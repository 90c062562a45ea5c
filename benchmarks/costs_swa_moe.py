"""What the window/global grouped-query routed family's work needs in
operations and bytes, from the configuration's published shapes, the share
of the experts the chip holds, and the dispatch records' counters.

As ``costs_mla_moe.py``: the work the algorithm needs ON THIS CHIP, not what
the program does to get there. Needed bytes of a dispatch are the weights
outside the routed experts once a micro-step, each HELD expert that got a
token once (its three matrices), and the pages the attention kernel has to
read BY KIND: a global layer's under the context, a window layer's under
the window. Operations are those of the parameters a token really uses
here: attention, the dense layer, the shared expert, the router, and the
(token, expert) pairs whose expert this chip holds; pairs that fell to the
other chips of the layer are their work, not this one's.
"""

from dataclasses import dataclass
from typing import Tuple

from benchmarks.costs import roofline_seconds  # noqa: F401 - max(ops, bytes)

_ITEM_BYTES = {"bfloat16": 2, "float32": 4}
_KINDS = {"sliding_attention": "window", "full_attention": "global"}


@dataclass(frozen=True)
class SwaMoeShape:
    """The sizes of the decoder as run, from a published config.json under
    its own key names (see ``swa_moe_shape``)."""

    n_layer: int            # as run here
    n_dense_layer: int      # first_k_dense_replace
    layer_kinds: Tuple[str, ...]    # "window" / "global", one a layer run
    window: int             # sliding_window
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    d_ff: int               # intermediate_size (dense layers)
    n_experts: int          # the router's outputs (published num_experts)
    experts_held: int       # num_experts as run: this chip's share
    first_expert: int
    experts_per_token: int
    d_expert: int           # moe_intermediate_size
    n_shared_experts: int
    routed_scaling_factor: float
    rope_theta: float
    rms_norm_eps: float
    n_positions: int        # served (max_position_embeddings as reduced)
    vocab_size: int         # as run: this chip's rows of the vocabulary
    dtype: str = "bfloat16"     # parameters and cache; float32 in tests

    @property
    def param_bytes(self) -> int:
        return _ITEM_BYTES[self.dtype]

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.n_dense_layer

    @property
    def kv_width(self) -> int:
        return self.n_kv_head * self.head_dim

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_kinds)


def swa_moe_shape(config: dict) -> SwaMoeShape:
    """From a configuration file that keeps the published keys. The lists a
    layer (``layer_types``, ``mlp_layer_types``) are kept whole, as
    published, and the first ``num_hidden_layers`` entries are run."""
    dtype = config.get("dtype", "bfloat16")
    if dtype not in _ITEM_BYTES:
        raise ValueError(f"dtype {dtype!r}: one of {sorted(_ITEM_BYTES)}")
    for key, only in (("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key) != only:
            raise ValueError(f"{key}: only {only!r} is implemented, the "
                             f"configuration says {config.get(key)!r}")
    rope = config["rope_parameters"]
    if rope.get("rope_type") != "default":
        raise ValueError("only the unscaled (default) rotary form is "
                         f"implemented, not {rope.get('rope_type')!r}")
    n_layer = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    mlp = list(config["mlp_layer_types"][:n_layer])
    if mlp != ["dense"] * dense + ["sparse"] * (n_layer - dense):
        raise ValueError("mlp_layer_types: the first first_k_dense_replace "
                         f"layers dense, the rest sparse; got {mlp}")
    kinds = tuple(_KINDS[k] for k in config["layer_types"][:n_layer])
    window = int(config["sliding_window"])
    if list(config["sliding_windows"][:n_layer]) != [
            window if k == "window" else 0 for k in kinds]:
        raise ValueError("sliding_windows disagrees with layer_types")
    share = config["expert_share"]
    held = int(config["num_experts"])
    if held != int(share["experts_held"]):
        raise ValueError("num_experts (as run) is the share's experts_held")
    return SwaMoeShape(
        n_layer=n_layer, n_dense_layer=dense, layer_kinds=kinds,
        window=window,
        d_model=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        n_kv_head=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        n_experts=int(share["router_outputs"]),
        experts_held=held, first_expert=int(share["first_expert"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_shared_experts=int(config["num_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        n_positions=int(config["max_position_embeddings"]),
        vocab_size=int(config["vocab_size"]),
        dtype=dtype,
    )


def attention_params(s: SwaMoeShape) -> int:
    """W_q, W_k, W_v and W_o of one layer."""
    q = s.n_head * s.head_dim
    return 2 * s.d_model * q + 2 * s.d_model * s.kv_width


def expert_params(s: SwaMoeShape) -> int:
    """One routed expert: gate, up and down."""
    return 3 * s.d_model * s.d_expert


def dense_ffn_params(s: SwaMoeShape) -> int:
    return 3 * s.d_model * s.d_ff


def shared_params(s: SwaMoeShape) -> int:
    """What every token of an expert layer reads besides attention and its
    routed experts: the shared expert(s) and the router (all its outputs:
    every chip routes over every expert)."""
    return s.n_shared_experts * expert_params(s) + s.d_model * s.n_experts


def param_count(s: SwaMoeShape) -> int:
    """Every matrix this chip holds (norm vectors and the router's bias left
    out: under a hundredth of a percent)."""
    return (s.n_layer * attention_params(s)
            + s.n_dense_layer * dense_ffn_params(s)
            + s.n_moe_layer * (s.experts_held * expert_params(s)
                               + shared_params(s))
            + 2 * s.vocab_size * s.d_model)


def fixed_weight_bytes(s: SwaMoeShape) -> int:
    """Parameters a forward step reads once whatever the routing: attention
    of every layer, the dense layers, shared experts and routers, and the
    output head. The input embedding is read by row and not counted."""
    return s.param_bytes * (
        s.n_layer * attention_params(s)
        + s.n_dense_layer * dense_ffn_params(s)
        + s.n_moe_layer * shared_params(s) + s.vocab_size * s.d_model)


def kv_bytes_per_position(s: SwaMoeShape) -> int:
    """Keys and values of one position in ONE layer."""
    return 2 * s.kv_width * s.param_bytes


def page_bytes_by_kind(s: SwaMoeShape, block_size: int) -> Tuple[int, int]:
    """Bytes one page stands for over the layers of each kind: (global,
    window)."""
    page = block_size * kv_bytes_per_position(s)
    return s.layers_of("global") * page, s.layers_of("window") * page


def attend_flops(s: SwaMoeShape, global_keys: float,
                 window_keys: float) -> float:
    """``q . k`` and ``p . v`` over the head size, per query head, for the
    (row, key) pairs attended in a layer of each kind."""
    pair = 4.0 * s.n_head * s.head_dim
    return pair * (s.layers_of("global") * global_keys
                   + s.layers_of("window") * window_keys)


def expected_pairs_held(s: SwaMoeShape) -> float:
    """Of a token's chosen experts in one layer, how many this chip holds
    when the router spreads evenly: top-k x held / outputs."""
    return s.experts_per_token * s.experts_held / s.n_experts


def token_flops(s: SwaMoeShape, with_head: bool = True,
                pairs_held: float = None) -> float:
    """Operations to push one token through the matrices it uses on this
    chip (2 per multiply-add), attention's keys apart (``attend_flops``).
    ``pairs_held``: (token, expert) pairs a layer on this chip, the even
    router's where not given."""
    pairs = expected_pairs_held(s) if pairs_held is None else pairs_held
    active = (s.n_layer * attention_params(s)
              + s.n_dense_layer * dense_ffn_params(s)
              + s.n_moe_layer * (pairs * expert_params(s)
                                 + shared_params(s)))
    head = s.d_model * s.vocab_size if with_head else 0
    return 2.0 * (active + head)


def _pairs_here(record: dict) -> float:
    """(token, expert) pairs the dispatch computed on this chip, over its
    expert layers and micro-steps: the held experts' histogram, summed."""
    return record["expert_load_mean"] * record["experts_held"]


def attention_work(s: SwaMoeShape, record: dict, block_size: int):
    """What the attention of one dispatch record needs, of all its layers:
    operations over the keys attended (a global layer's rows attend their
    context, a window layer's at most the window; the lanes' mean context
    stands for each) and the bytes of the pages read by kind
    (``ctx_pages_global`` / ``ctx_pages_window``, each over its kind's
    layers). None where the record is not split by kind or of another
    phase."""
    if "ctx_pages_window" not in record or not record.get("batch_size"):
        return None
    lanes, tokens = record["batch_size"], record["tokens"]
    steps = record["micro_steps"]
    if record["phase"] == "decode":
        # micro-step i holds ctx_tokens + lanes * i positions in all
        keys = steps * record["ctx_tokens"] + lanes * steps * (steps - 1) / 2
        per_row = keys / tokens
    elif record["phase"] == "prefill_chunk":
        # a lane of n rows ending at context c: a row attends c - (n - 1) / 2
        # positions on average
        per_row = record["ctx_tokens"] / lanes - (tokens / lanes - 1) / 2
        keys = tokens * per_row
    else:
        return None
    g_bytes, w_bytes = page_bytes_by_kind(s, block_size)
    return {"flops": float(attend_flops(
                s, keys, tokens * min(per_row, s.window))),
            "bytes": float(record["ctx_pages_global"] * g_bytes
                           + record["ctx_pages_window"] * w_bytes)}


def dispatch_work(s: SwaMoeShape, record: dict, block_size: int):
    """The work of one stepscope dispatch record of this family, or None
    where the record is of another phase or family, or has no routing
    counters yet (the delivery thread had not read them).

    Bytes: the fixed weights once a micro-step, each held expert that got a
    token once (``experts_hit``), the pages read by kind. Operations: every
    token through the fixed matrices, the pairs really computed here through
    an expert each, the head (every decode token; a prefill lane's last
    row), and attention (``attention_work``)."""
    attention = attention_work(s, record, block_size)
    if attention is None or "experts_hit" not in record:
        return None
    tokens = record["tokens"]
    heads = tokens if record["phase"] == "decode" else record["batch_size"]
    flops = (tokens * token_flops(s, with_head=False, pairs_held=0.0)
             + 2.0 * _pairs_here(record) * expert_params(s)
             + heads * 2.0 * s.d_model * s.vocab_size
             + attention["flops"])
    nbytes = (record["micro_steps"] * fixed_weight_bytes(s)
              + record["experts_hit"] * expert_params(s) * s.param_bytes
              + attention["bytes"])
    return {"flops": float(flops), "bytes": float(nbytes)}
