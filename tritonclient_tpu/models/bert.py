"""BERT encoder, TPU-first: functional pure-JAX, scan-stacked layers.

This is the flagship compute model behind the BASELINE.json BERT-base
benchmark configs ("perf_analyzer concurrency sweep — BERT-base"). Design
choices for the MXU/XLA:

  * layers stored stacked along a leading [n_layers, ...] axis and executed
    with `lax.scan` — one compiled layer body, no Python unrolling;
  * bfloat16 params/activations, float32 softmax/LayerNorm accumulation;
  * Megatron-style tensor-parallel partition rules (qkv/ffn-in column,
    proj/ffn-out row) — GSPMD inserts the psums;
  * sequence axis shardable on 'sp' with ring attention
    (tritonclient_tpu.parallel.ring_attention) for long context.

Serving-side, `BertBaseModel` exposes it through the same Model contract the
KServe v2 front-ends execute (reference client drives it like any Triton
model, e.g. via perf-analyzer configs in BASELINE.json).
"""

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from tritonclient_tpu.models._base import Model, TensorSpec
from tritonclient_tpu.ops.attention import dot_product_attention


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    layer_norm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def bert_base() -> BertConfig:
    return BertConfig()


def bert_tiny(seq_len: int = 64) -> BertConfig:
    """Small config for tests and multi-chip dry-runs."""
    return BertConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_len=seq_len, dtype=jnp.float32,
    )


# --------------------------------------------------------------------------- #
# params                                                                      #
# --------------------------------------------------------------------------- #


def init_params(key: jax.Array, cfg: BertConfig) -> Dict:
    d, f, n = cfg.d_model, cfg.d_ff, cfg.n_layers
    keys = iter(jax.random.split(key, 16))

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    params = {
        "embed": {
            "tok": dense(next(keys), (cfg.vocab_size, d), d),
            "pos": dense(next(keys), (cfg.max_len, d), d),
            "typ": dense(next(keys), (cfg.type_vocab, d), d),
            "ln_scale": jnp.ones((d,), cfg.dtype),
            "ln_bias": jnp.zeros((d,), cfg.dtype),
        },
        "layers": {
            "wqkv": dense(next(keys), (n, d, 3 * d), d),
            "bqkv": jnp.zeros((n, 3 * d), cfg.dtype),
            "wo": dense(next(keys), (n, d, d), d),
            "bo": jnp.zeros((n, d), cfg.dtype),
            "ln1_scale": jnp.ones((n, d), cfg.dtype),
            "ln1_bias": jnp.zeros((n, d), cfg.dtype),
            "w_in": dense(next(keys), (n, d, f), d),
            "b_in": jnp.zeros((n, f), cfg.dtype),
            "w_out": dense(next(keys), (n, f, d), f),
            "b_out": jnp.zeros((n, d), cfg.dtype),
            "ln2_scale": jnp.ones((n, d), cfg.dtype),
            "ln2_bias": jnp.zeros((n, d), cfg.dtype),
        },
        "pooler": {
            "w": dense(next(keys), (d, d), d),
            "b": jnp.zeros((d,), cfg.dtype),
        },
        "mlm": {
            "w": dense(next(keys), (d, d), d),
            "b": jnp.zeros((d,), cfg.dtype),
            "ln_scale": jnp.ones((d,), cfg.dtype),
            "ln_bias": jnp.zeros((d,), cfg.dtype),
            "decoder_bias": jnp.zeros((cfg.vocab_size,), cfg.dtype),
        },
    }
    return params


# Megatron-style TP: qkv/ffn-in sharded on output dim (column), proj/ffn-out
# on input dim (row) — GSPMD inserts the all-reduces. fsdp (when present)
# shards the remaining large dim.
PARTITION_RULES = (
    (r"layers/wqkv", P(None, "fsdp", "tp")),
    (r"layers/bqkv", P(None, "tp")),
    (r"layers/wo", P(None, "tp", "fsdp")),
    (r"layers/w_in", P(None, "fsdp", "tp")),
    (r"layers/b_in", P(None, "tp")),
    (r"layers/w_out", P(None, "tp", "fsdp")),
    (r"embed/(tok|pos|typ)", P(None, None)),
    (r"mlm/w|pooler/w", P(None, "tp")),
    (r"mlm/decoder_bias", P()),
)


# --------------------------------------------------------------------------- #
# forward                                                                     #
# --------------------------------------------------------------------------- #


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        x.dtype
    )


def encode(
    params: Dict,
    tokens: jax.Array,
    cfg: BertConfig,
    *,
    type_ids: Optional[jax.Array] = None,
    attention_fn: Optional[Callable] = None,
    activation_spec: Optional[P] = None,
) -> jax.Array:
    """tokens [B, L] int32 → sequence output [B, L, d_model].

    ``attention_fn(q, k, v)`` defaults to single-device attention; pass a
    ring_attention closure for sp-sharded long sequences. ``activation_spec``
    (e.g. P('dp', 'sp', None)) pins the hidden-state layout on the mesh.
    """
    atn = attention_fn or functools.partial(dot_product_attention, causal=False)
    emb = params["embed"]
    b, l = tokens.shape
    x = emb["tok"][tokens]
    x = x + emb["pos"][:l][None, :, :]
    type_ids = jnp.zeros_like(tokens) if type_ids is None else type_ids
    x = x + emb["typ"][type_ids]
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], cfg.layer_norm_eps)

    def constrain(h):
        if activation_spec is not None:
            return lax.with_sharding_constraint(h, activation_spec)
        return h

    x = constrain(x)

    def layer(h, lp):
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (b, l, cfg.n_heads, cfg.head_dim)
        out = atn(q.reshape(shape), k.reshape(shape), v.reshape(shape))
        out = out.reshape(b, l, cfg.d_model) @ lp["wo"] + lp["bo"]
        h = _layer_norm(h + out, lp["ln1_scale"], lp["ln1_bias"],
                        cfg.layer_norm_eps)
        ff = jax.nn.gelu(h @ lp["w_in"] + lp["b_in"])
        ff = ff @ lp["w_out"] + lp["b_out"]
        h = _layer_norm(h + ff, lp["ln2_scale"], lp["ln2_bias"],
                        cfg.layer_norm_eps)
        return constrain(h), None

    x, _ = lax.scan(layer, x, params["layers"])
    return x


def pooled_output(params: Dict, seq_out: jax.Array) -> jax.Array:
    """[CLS] (position 0) through the tanh pooler → [B, d_model]."""
    cls = seq_out[:, 0, :]
    return jnp.tanh(cls @ params["pooler"]["w"] + params["pooler"]["b"])


def mlm_logits(params: Dict, seq_out: jax.Array, cfg: BertConfig) -> jax.Array:
    """Masked-LM head, decoder tied to the token embedding: [B, L, vocab]."""
    h = jax.nn.gelu(seq_out @ params["mlm"]["w"] + params["mlm"]["b"])
    h = _layer_norm(h, params["mlm"]["ln_scale"], params["mlm"]["ln_bias"],
                    cfg.layer_norm_eps)
    return h @ params["embed"]["tok"].T + params["mlm"]["decoder_bias"]


def mlm_loss(params: Dict, batch: Dict, cfg: BertConfig, **encode_kw) -> jax.Array:
    """Mean cross-entropy over all positions of batch['labels']."""
    seq = encode(params, batch["tokens"], cfg, **encode_kw)
    logits = mlm_logits(params, seq, cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -ll.mean()


# --------------------------------------------------------------------------- #
# serving model                                                               #
# --------------------------------------------------------------------------- #


class BertBaseModel(Model):
    """Serves BERT-base: INPUT_IDS int32 [-1, L] → POOLED_OUTPUT fp32 [-1, 768].

    The wire contract keeps responses small (pooled vector, not the [B, L, V]
    logits) so benchmarks measure model compute + transport, matching how the
    reference's perf_analyzer drives BERT (BASELINE.json configs).
    """

    name = "bert_base"
    platform = "jax"
    dynamic_batching = True
    max_batch_size = 32

    def __init__(self, cfg: Optional[BertConfig] = None, seed: int = 0,
                 use_flash_attention: bool = False,
                 checkpoint: Optional[str] = None,
                 mesh=None, sequence_parallel_impl: str = "ring"):
        """``mesh``: serve mesh-sharded — params laid out by
        PARTITION_RULES, activations constrained to (dp/fsdp, sp), and,
        when the mesh has an sp axis > 1, ring or Ulysses sequence-
        parallel attention so long sequences never congregate on one
        chip. Pairs with mesh-spanning shm regions
        (utils/tpu_shared_memory.create_sharded_memory_region): the
        served tokens arrive as a sharded jax.Array and the pooled
        output parks back sharded — SURVEY §5.7/§5.8 serving-side.
        """
        super().__init__()
        self.cfg = cfg or bert_base()
        self.inputs = [TensorSpec("INPUT_IDS", "INT32", [-1, -1])]
        self.outputs = [
            TensorSpec("POOLED_OUTPUT", "FP32", [-1, self.cfg.d_model])
        ]
        self.mesh = mesh
        if mesh is not None:
            # Mesh-sharded serving has shape-alignment contracts (batch %
            # dp*fsdp, seq % sp); the dynamic batcher's pow2 row padding
            # cannot honor them, so batching is disabled per instance.
            self.dynamic_batching = False
        if checkpoint is not None:
            from tritonclient_tpu.models.checkpoint import load_params

            self._params = load_params(checkpoint)
        elif mesh is not None:
            # Initialize DIRECTLY sharded — no single-device staging copy
            # (parallel/sharding.init_sharded).
            from tritonclient_tpu.parallel.sharding import init_sharded

            self._params = init_sharded(
                mesh, lambda k: init_params(k, self.cfg),
                PARTITION_RULES, jax.random.PRNGKey(seed),
            )
        else:
            self._params = init_params(jax.random.PRNGKey(seed), self.cfg)

        attention_fn = None
        activation_spec = None
        self._data_sharding = None
        # Which attention this instance was built with; attention_path()
        # refines it per sequence length (benchmarks print it).
        self.attention_impl = "reference"
        if mesh is not None:
            from tritonclient_tpu.parallel.sharding import (
                named_sharding,
                shard_tree,
            )

            # No-op for init_sharded params; lays out checkpoint restores.
            self._params = shard_tree(mesh, self._params, PARTITION_RULES)
            activation_spec = named_sharding(
                mesh, ("dp", "fsdp"), "sp", None
            )
            self._data_sharding = named_sharding(mesh, ("dp", "fsdp"), "sp")
            if mesh.shape.get("sp", 1) > 1:
                impl = "flash" if use_flash_attention else "reference"
                if sequence_parallel_impl == "ulysses":
                    from tritonclient_tpu.parallel.ulysses import (
                        ulysses_attention,
                    )

                    attention_fn = functools.partial(
                        ulysses_attention, mesh=mesh, impl=impl
                    )
                    self.attention_impl = f"ulysses-{impl}"
                else:
                    from tritonclient_tpu.parallel.ring_attention import (
                        ring_attention,
                    )

                    attention_fn = functools.partial(
                        ring_attention, mesh=mesh, impl=impl
                    )
                    self.attention_impl = f"ring-{impl}"
        if attention_fn is None and use_flash_attention:
            # Tile-streamed Pallas kernel (ops/flash_attention.py): pays off
            # at long sequence where the [L, L] scores stop fitting HBM;
            # shapes that don't tile fall back automatically.
            from tritonclient_tpu.ops.flash_attention import flash_attention

            attention_fn = functools.partial(flash_attention, causal=False)
            self.attention_impl = "flash"

        @jax.jit
        def fwd(params, tokens):
            seq = encode(params, tokens, self.cfg, attention_fn=attention_fn,
                         activation_spec=activation_spec)
            return pooled_output(params, seq).astype(jnp.float32)

        self._fwd = fwd
        # Parameter bytes on the device-memory ledger (per-device, from
        # the actual shardings — registered AFTER the mesh layout so a
        # tp/fsdp split reports split bytes).
        from tritonclient_tpu import _memscope

        _memscope.register_params(self.name, self._params)

    def attention_path(self, seq_len: int) -> str:
        """What the forward's attention resolves to at ``seq_len``: the
        build-time ``attention_impl``, with single-device flash refined to
        ``flash-mosaic`` / ``flash-interpret`` / ``flash-reference`` (the
        kernel compiled, interpreted, or silently replaced because the
        sequence does not tile — ops.flash_attention_path)."""
        if self.attention_impl != "flash":
            return self.attention_impl
        from tritonclient_tpu.ops.flash_attention import flash_attention_path

        shape = (1, seq_len, self.cfg.n_heads, self.cfg.head_dim)
        return "flash-" + flash_attention_path(shape, shape)

    def infer(self, inputs, parameters=None):
        x = inputs["INPUT_IDS"]
        if self.mesh is not None:
            self._check_mesh_alignment(x.shape)
        if isinstance(x, jax.Array):
            # Zero-copy path (tpu shm): the tokens are already on device —
            # no host round-trip.
            tokens = x if x.dtype == jnp.int32 else x.astype(jnp.int32)
            if self._data_sharding is not None and tokens.sharding.device_set != set(
                self.mesh.devices.flat
            ):
                # e.g. a single-device region feeding a mesh model: the
                # jit requires params and inputs on one device set.
                tokens = jax.device_put(tokens, self._data_sharding)
        else:
            tokens = jnp.asarray(np.asarray(x, dtype=np.int32))
            if self._data_sharding is not None:
                tokens = jax.device_put(tokens, self._data_sharding)
        out = self._fwd(self._params, tokens)
        # Return the device array un-materialized; the response path parks it
        # in a tpu shm region zero-copy or serializes it for the wire.
        return {"POOLED_OUTPUT": out}

    def _check_mesh_alignment(self, shape):
        """Mesh-sharded serving contract: batch % (dp*fsdp), seq % sp."""
        mshape = self.mesh.shape
        brow = mshape.get("dp", 1) * mshape.get("fsdp", 1)
        sp = mshape.get("sp", 1)
        b, l = int(shape[0]), int(shape[1])
        if b % brow or l % sp:
            raise ValueError(
                f"mesh-sharded {self.name} requires batch divisible by "
                f"{brow} (dp*fsdp) and sequence length divisible by {sp} "
                f"(sp); got [{b}, {l}]"
            )

    def warmup(self):
        b, l = 1, 128
        if self.mesh is not None:
            # Minimal shape whose dims divide the mesh's data axes (seq
            # clamped to a multiple of sp within max_len).
            shape = self.mesh.shape
            sp = shape.get("sp", 1)
            b = max(shape.get("dp", 1) * shape.get("fsdp", 1), 1)
            l = min(16 * sp, self.cfg.max_len // sp * sp)
            l = max(l, sp)
        out = self.infer({"INPUT_IDS": np.zeros((b, l), np.int32)})
        jax.block_until_ready(out["POOLED_OUTPUT"])
