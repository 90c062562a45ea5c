"""What the MLA / routed-expert family's work needs in operations and bytes,
from the configuration's published shapes and the router's counters.

As ``costs.py`` for the GPT family: the work the algorithm needs, not what
the program does to get there. Needed bytes of a dispatch are the weights
outside the experts once, each expert that got a token once (its three
matrices), and the latent cache the requests hold; operations are those of
the ACTIVE parameters (the experts a token is routed to, the shared expert,
attention in its expanded form) for the tokens really processed.
"""

from dataclasses import dataclass


_ITEM_BYTES = {"bfloat16": 2, "float32": 4}


@dataclass(frozen=True)
class MlaMoeShape:
    """The sizes of a DeepSeek-V3-shaped decoder, from a published
    config.json under its own key names (see ``mla_moe_shape``)."""

    n_layer: int            # as run here
    n_dense_layer: int      # first_k_dense_replace
    d_model: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int               # intermediate_size (dense layers)
    n_experts: int
    experts_per_token: int
    d_expert: int           # moe_intermediate_size
    n_shared_experts: int
    routed_scaling_factor: float
    rope_theta: float
    rms_norm_eps: float
    n_positions: int        # served (max_position_embeddings as reduced)
    vocab_size: int
    dtype: str = "bfloat16"     # parameters and cache; float32 in tests

    @property
    def param_bytes(self) -> int:
        return _ITEM_BYTES[self.dtype]

    @property
    def kv_bytes(self) -> int:
        return _ITEM_BYTES[self.dtype]

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.n_dense_layer

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_moe_shape(config: dict) -> MlaMoeShape:
    """From a configuration file that keeps the published keys."""
    dtype = config.get("dtype", "bfloat16")
    if dtype not in _ITEM_BYTES:
        raise ValueError(f"dtype {dtype!r}: one of {sorted(_ITEM_BYTES)}")
    for key, only in (("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("rope_scaling", None),
                      ("rope_interleave", True), ("norm_topk_prob", True),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key) != only:
            raise ValueError(f"{key}: only {only!r} is implemented, the "
                             f"configuration says {config.get(key)!r}")
    return MlaMoeShape(
        n_layer=int(config["num_hidden_layers"]),
        n_dense_layer=int(config["first_k_dense_replace"]),
        d_model=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        n_experts=int(config["n_routed_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_shared_experts=int(config["n_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        n_positions=int(config["max_position_embeddings"]),
        vocab_size=int(config["vocab_size"]),
        dtype=dtype,
    )


def attention_params(s: MlaMoeShape) -> int:
    """The five matrices of one layer's attention."""
    h = s.n_head
    return (s.d_model * s.q_lora_rank + s.q_lora_rank * h * s.qk_head_dim
            + s.d_model * s.latent_dim
            + s.kv_lora_rank * h * (s.qk_nope_head_dim + s.v_head_dim)
            + h * s.v_head_dim * s.d_model)


def expert_params(s: MlaMoeShape) -> int:
    """One routed expert: gate, up and down."""
    return 3 * s.d_model * s.d_expert


def dense_ffn_params(s: MlaMoeShape) -> int:
    return 3 * s.d_model * s.d_ff


def shared_params(s: MlaMoeShape) -> int:
    """What every token of an expert layer reads besides attention and its
    routed experts: the shared expert(s) and the router."""
    return s.n_shared_experts * expert_params(s) + s.d_model * s.n_experts


def param_count(s: MlaMoeShape) -> int:
    """Every matrix held (norm vectors and the router's bias left out: under
    a hundredth of a percent)."""
    return (s.n_layer * attention_params(s)
            + s.n_dense_layer * dense_ffn_params(s)
            + s.n_moe_layer * (s.n_experts * expert_params(s)
                               + shared_params(s))
            + 2 * s.vocab_size * s.d_model)


def fixed_weight_bytes(s: MlaMoeShape) -> int:
    """Parameters a forward step reads once whatever the routing: attention
    of every layer, the dense layers, shared experts and routers, and the
    output head. The input embedding is read by row and not counted."""
    return s.param_bytes * (
        s.n_layer * attention_params(s)
        + s.n_dense_layer * dense_ffn_params(s)
        + s.n_moe_layer * shared_params(s) + s.vocab_size * s.d_model)


def latent_bytes_per_position(s: MlaMoeShape) -> int:
    """What one position keeps in the cache, over every layer."""
    return s.n_layer * s.latent_dim * s.kv_bytes


def attend_flops(s: MlaMoeShape, positions: float) -> float:
    """``q . k`` over ``nope + rope`` and ``p . v`` over ``v``, per head and
    layer, for ``positions`` attended positions in all."""
    return 2.0 * s.n_layer * s.n_head * (s.qk_head_dim
                                         + s.v_head_dim) * positions


def token_flops(s: MlaMoeShape, context: float, with_head: bool = True) -> float:
    """Operations to push one token through the model with ``context``
    positions to attend (its own included): 2 per multiply-add in the
    matrices the token really uses (top-k experts, not all), and the
    attention over its context (``attend_flops``)."""
    active = (s.n_layer * attention_params(s)
              + s.n_dense_layer * dense_ffn_params(s)
              + s.n_moe_layer * (s.experts_per_token * expert_params(s)
                                 + shared_params(s)))
    head = s.d_model * s.vocab_size if with_head else 0
    return 2.0 * (active + head) + attend_flops(s, context)


def decode_dispatch(s: MlaMoeShape, active: int, micro_steps: int,
                    ctx_tokens: float, experts_hit: int) -> dict:
    """One decode dispatch, from its stepscope record: ``active`` slots
    (``batch_size``) advance ``micro_steps`` tokens each; ``ctx_tokens`` is
    the context the active slots hold at the first micro-step (each later
    one holds ``active`` more); ``experts_hit`` counts the distinct experts
    that got a token, summed over expert layers and micro-steps. Every
    micro-step reads the fixed weights once and each expert it hits once."""
    held = micro_steps * ctx_tokens + active * micro_steps * (micro_steps - 1) / 2
    flops = (micro_steps * active * token_flops(s, 0.0)
             + attend_flops(s, held))
    nbytes = (micro_steps * fixed_weight_bytes(s)
              + experts_hit * expert_params(s) * s.param_bytes
              + held * latent_bytes_per_position(s))
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_dispatch(s: MlaMoeShape, lanes: int, tokens: int,
                     ctx_tokens: float, experts_hit: int) -> dict:
    """One prefill-chunk dispatch, from its record: ``lanes`` prompts
    (``batch_size``) feed ``tokens`` positions in all and hold
    ``ctx_tokens`` once the chunk is in. A lane of n tokens ending at
    context c attends c - (n - 1) / 2 positions a row on average (the lanes'
    means stand for each lane); it reads the latent it holds once and
    computes the head for its last row only."""
    n_mean, c_mean = tokens / lanes, ctx_tokens / lanes
    attended = tokens * (c_mean - (n_mean - 1) / 2)
    flops = (tokens * token_flops(s, 0.0, with_head=False)
             + attend_flops(s, attended)
             + lanes * 2.0 * s.d_model * s.vocab_size)
    nbytes = (fixed_weight_bytes(s)
              + experts_hit * expert_params(s) * s.param_bytes
              + ctx_tokens * latent_bytes_per_position(s))
    return {"flops": float(flops), "bytes": float(nbytes)}


def dispatch_work(s: MlaMoeShape, record: dict):
    """The work of one stepscope dispatch record of this family, or None
    where the record is of another phase or has no routing counters (the
    delivery thread had not read them yet)."""
    if "experts_hit" not in record or not record.get("batch_size"):
        return None
    if record["phase"] == "decode":
        return decode_dispatch(s, record["batch_size"], record["micro_steps"],
                               record["ctx_tokens"], record["experts_hit"])
    if record["phase"] == "prefill_chunk":
        return prefill_dispatch(s, record["batch_size"], record["tokens"],
                                record["ctx_tokens"], record["experts_hit"])
    return None


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and needed bytes over peak bytes/s."""
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
