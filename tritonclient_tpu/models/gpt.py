"""GPT-style causal decoder with KV-cache generation: the LLM serving path.

The reference ecosystem's LLM instrument (genai-perf, relocated out of the
snapshot — reference src/c++/perf_analyzer/genai-perf/README.md) measures
time-to-first-token and inter-token latency against a server streaming one
response per generated token. This model is that server side, TPU-first:

  * pre-LN decoder, layers stacked and scanned (`lax.scan`) so XLA compiles
    ONE layer body regardless of depth;
  * prefill = full-sequence causal attention (flash kernel optional) that
    also writes the KV cache in one pass;
  * decode = jit-compiled single-token step with donated cache buffers
    (in-place dynamic_update_slice, no reallocation per token) and a
    length-masked attention over the static-shape cache — static shapes
    and donation are what keep XLA from recompiling or copying per token;
  * generation comes in two forms: `generate_tokens` (a Python loop
    yielding one token at a time — the decoupled streaming server path)
    and `generate_scan` (one jit of the whole loop via lax.scan — the
    throughput/bench path and the cross-check for the cache math).

Weights are randomly initialized (like BertBaseModel): the serving/bench
surface measures transport + compute, not checkpoint quality.
"""

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from tritonclient_tpu.models._base import Model, TensorSpec
from tritonclient_tpu.models.bert import _layer_norm
from tritonclient_tpu.ops.attention import dot_product_attention


@dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    d_ff: int = 2048
    max_len: int = 512
    layer_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def gpt_small() -> GptConfig:
    return GptConfig()


def gpt_tiny(max_len: int = 64) -> GptConfig:
    """Small config for tests and CPU runs."""
    return GptConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_len=max_len, dtype=jnp.float32,
    )


def init_params(key: jax.Array, cfg: GptConfig) -> Dict:
    d, f, n = cfg.d_model, cfg.d_ff, cfg.n_layers
    keys = iter(jax.random.split(key, 8))

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    return {
        "embed": {
            "tok": dense(next(keys), (cfg.vocab_size, d), d),
            "pos": dense(next(keys), (cfg.max_len, d), d),
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
        "final_ln": {
            "scale": jnp.ones((d,), cfg.dtype),
            "bias": jnp.zeros((d,), cfg.dtype),
        },
    }


# Same Megatron TP layout as BERT (models/bert.py PARTITION_RULES): qkv and
# ffn-in column-sharded, proj and ffn-out row-sharded; GSPMD inserts the
# all-reduces.
PARTITION_RULES = (
    (r"layers/wqkv", P(None, "fsdp", "tp")),
    (r"layers/bqkv", P(None, "tp")),
    (r"layers/wo", P(None, "tp", "fsdp")),
    (r"layers/w_in", P(None, "fsdp", "tp")),
    (r"layers/b_in", P(None, "tp")),
    (r"layers/w_out", P(None, "tp", "fsdp")),
    (r"embed/(tok|pos)", P(None, None)),
)


# --------------------------------------------------------------------------- #
# forward / prefill                                                           #
# --------------------------------------------------------------------------- #


def _layer_fn(h, lp, cfg: GptConfig, atn: Callable):
    """One pre-LN decoder layer; returns (h, (k, v)) for cache writers."""
    b, l = h.shape[0], h.shape[1]
    a = _layer_norm(h, lp["ln1_scale"], lp["ln1_bias"], cfg.layer_norm_eps)
    qkv = a @ lp["wqkv"] + lp["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, l, cfg.n_heads, cfg.head_dim)
    q, k, v = (t.reshape(shape) for t in (q, k, v))
    out = atn(q, k, v)
    h = h + (out.reshape(b, l, cfg.d_model) @ lp["wo"] + lp["bo"])
    m = _layer_norm(h, lp["ln2_scale"], lp["ln2_bias"], cfg.layer_norm_eps)
    h = h + (jax.nn.gelu(m @ lp["w_in"] + lp["b_in"]) @ lp["w_out"]
             + lp["b_out"])
    return h, (k, v)


def _embed(params: Dict, tokens: jax.Array) -> jax.Array:
    l = tokens.shape[1]
    return params["embed"]["tok"][tokens] + params["embed"]["pos"][:l][None]


def _head(params: Dict, x: jax.Array, cfg: GptConfig) -> jax.Array:
    x = _layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"],
                    cfg.layer_norm_eps)
    return (x.astype(jnp.float32)
            @ params["embed"]["tok"].astype(jnp.float32).T)


def forward(
    params: Dict,
    tokens: jax.Array,
    cfg: GptConfig,
    *,
    attention_fn: Optional[Callable] = None,
) -> jax.Array:
    """tokens [B, L] int32 → logits [B, L, vocab] (no cache)."""
    atn = attention_fn or functools.partial(
        dot_product_attention, causal=True
    )
    x, _ = lax.scan(
        lambda h, lp: (_layer_fn(h, lp, cfg, atn)[0], None),
        _embed(params, tokens), params["layers"],
    )
    return _head(params, x, cfg)


def init_cache(cfg: GptConfig, batch: int) -> Tuple[jax.Array, jax.Array]:
    """(k, v) caches, each [n_layers, B, max_len, H, head_dim]."""
    shape = (cfg.n_layers, batch, cfg.max_len, cfg.n_heads, cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def prefill(params: Dict, tokens: jax.Array, cfg: GptConfig,
            attention_fn: Optional[Callable] = None):
    """Full causal pass over the prompt, filling the KV cache.

    tokens [B, L] → (logits_last [B, vocab], (k_cache, v_cache)).
    ``attention_fn(q, k, v)`` must be causal; pass a flash_attention
    closure for long prompts. (``decode_step`` attends this contiguous
    cache with a masked einsum; the paged engine's decode and prefill
    read their pages through ``ops/paged_attention.py``.)
    """
    atn = attention_fn or functools.partial(
        dot_product_attention, causal=True
    )
    b = tokens.shape[0]
    x, (ks, vs) = lax.scan(
        functools.partial(_layer_fn, cfg=cfg, atn=atn),
        _embed(params, tokens), params["layers"],
    )
    logits = _head(params, x[:, -1:], cfg)[:, 0]
    k_cache, v_cache = init_cache(cfg, b)
    # ks/vs: [n_layers, B, L, H, Dh] — place the prompt at positions [0, L).
    k_cache = lax.dynamic_update_slice(k_cache, ks.astype(cfg.dtype),
                                       (0, 0, 0, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, vs.astype(cfg.dtype),
                                       (0, 0, 0, 0, 0))
    return logits, (k_cache, v_cache)


def _masked_cache_attention(q, kc, vc, mask):
    """q [N, H, Dh] against a dense cache kc/vc [N, L, H, Dh] → [N, H, Dh]
    float32; ``mask`` broadcasts against the [N, H, L] scores. The
    contiguous cache's attention, and the float32 reference the paged
    kernel (``ops/paged_attention.py``) is tested against: one query row
    a cache is bound by the cache read, which a masked einsum does in one
    pass over a cache that is dense."""
    s = jnp.einsum(
        "nhd,nlhd->nhl",
        q.astype(jnp.float32) / np.sqrt(q.shape[-1]),
        kc.astype(jnp.float32),
    )
    s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhl,nlhd->nhd", p, vc.astype(jnp.float32))


def _decode_layer(h, lp, kc, vc, cfg: GptConfig, write_kv, attend):
    """Single-token decoder layer, shared by the per-request decode path
    (`decode_step`) and the continuous-batching slot bank
    (models/gpt_engine.py) — one source of truth for the LN/QKV/
    attention/MLP math, parameterized only by how the new token's K/V
    enter the cache and how the cache is attended.

    h [N, d]; ``write_kv(kc, vc, k, v)`` inserts the [N, H, Dh]
    projections into kc/vc; ``attend(q, kc, vc)`` gives the [N, H, Dh]
    attention of the queries over the written cache. The contiguous path
    holds kc/vc [N, L, H, Dh] and attends by ``_masked_cache_attention``;
    the paged engine passes its whole [L, n_blocks, bs, H * Dh] pools,
    which only its ``write_kv`` (a scatter at layer, page, offset) and its
    ``attend`` (the paged-attention kernel, which reads the pages a table
    holds) index. Under tensor parallelism ``wo`` and ``w_out`` are
    row-sharded and GSPMD puts one all-reduce behind each: two a layer.
    """
    n = h.shape[0]
    a = _layer_norm(h, lp["ln1_scale"], lp["ln1_bias"], cfg.layer_norm_eps)
    qkv = a @ lp["wqkv"] + lp["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    hd = (n, cfg.n_heads, cfg.head_dim)
    kc, vc = write_kv(kc, vc, k.reshape(hd), v.reshape(hd))
    out = attend(q.reshape(hd), kc, vc)
    out = out.reshape(n, cfg.d_model).astype(h.dtype)
    h = h + (out @ lp["wo"] + lp["bo"])
    m = _layer_norm(h, lp["ln2_scale"], lp["ln2_bias"], cfg.layer_norm_eps)
    h = h + (jax.nn.gelu(m @ lp["w_in"] + lp["b_in"]) @ lp["w_out"]
             + lp["b_out"])
    return h, (kc, vc)


def decode_step(params: Dict, k_cache, v_cache, token: jax.Array,
                pos: jax.Array, cfg: GptConfig):
    """One generation step against the cache.

    token [B] int32, pos scalar int32 (the position this token occupies) →
    (logits [B, vocab], k_cache, v_cache). Cache buffers should be donated
    by the jit wrapper so the update is in-place on device.
    """
    x = (params["embed"]["tok"][token]
         + params["embed"]["pos"][pos][None])          # [B, d]

    def write_kv(kc, vc, k, v):
        # Same scalar position for every batch row.
        kc = lax.dynamic_update_slice(
            kc, k[:, None].astype(kc.dtype), (0, pos, 0, 0)
        )
        vc = lax.dynamic_update_slice(
            vc, v[:, None].astype(vc.dtype), (0, pos, 0, 0)
        )
        return kc, vc

    attend = functools.partial(
        _masked_cache_attention,
        mask=(jnp.arange(cfg.max_len) <= pos)[None, None, :])

    def layer(h, xs):
        lp, kc, vc = xs
        return _decode_layer(h, lp, kc, vc, cfg, write_kv, attend)

    x, (k_cache, v_cache) = lax.scan(
        layer, x, (params["layers"], k_cache, v_cache)
    )
    return _head(params, x, cfg), k_cache, v_cache


@functools.lru_cache(maxsize=8)
def make_decode_fn(cfg: GptConfig):
    """Jit-compiled decode step with donated caches.

    Memoized per config: a fresh ``jax.jit`` object carries a fresh trace
    cache, so rebuilding it per request would retrace every request
    (TPU010). One shared callable serves every caller with that config.
    """
    step = functools.partial(decode_step, cfg=cfg)
    return jax.jit(step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=8)
def _prefill_fn(cfg: GptConfig):
    """Memoized prefill jit — same retrace argument as ``make_decode_fn``
    for the ``generate_tokens`` fallback path (TPU010)."""
    return jax.jit(functools.partial(prefill, cfg=cfg))


def sample_token(logits: jax.Array, key: jax.Array, temperature,
                 top_k) -> jax.Array:
    """logits [B, vocab] → token [B] int32.

    temperature <= 0 means greedy (exact argmax); top_k <= 0 disables the
    top-k filter. Both thresholds are traced values, so one compiled
    sampler serves every request's settings (the top-k cutoff is a
    dynamic gather into the sorted logits, not a static-k lax.top_k).
    """
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    scaled = logits.astype(jnp.float32) / t
    vocab = logits.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    k_idx = jnp.clip(jnp.asarray(top_k, jnp.int32) - 1, 0, vocab - 1)
    kth = jnp.where(top_k > 0, sorted_desc[..., k_idx], -jnp.inf)
    masked = jnp.where(scaled >= kth[..., None], scaled, -jnp.inf)
    sampled = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def sampling_key(seed, step) -> jax.Array:
    """The key schedule shared by every generation path: token index
    ``step`` (0 = the prefill-derived token) of a request seeded ``seed``
    always samples with the same key, so the single-request loop, the
    one-jit scan, and the continuous-batching engine produce identical
    sampled streams for the same (seed, prompt, settings).

    Seeds canonicalize to 31 bits here (works for Python ints and traced
    int32 alike), so any int64 wire value — including negatives — maps to
    the same key on every path and fits the engine's int32 slot vectors.
    """
    seed = seed & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


# tpulint: hot-path
def generate_tokens(
    params: Dict,
    prompt: np.ndarray,
    max_new: int,
    cfg: GptConfig,
    *,
    prefill_fn=None,
    decode_fn=None,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
) -> Iterator[np.ndarray]:
    """Generation, one token per yield — the streaming server path.

    Greedy by default; ``temperature``/``top_k``/``seed`` select sampled
    decoding on the shared (seed, step) key schedule (``sampling_key``).
    Each yield materializes one [B] int32 token on the host (that token
    is about to go out on the wire anyway) — but only AFTER the next
    step's dispatch is in flight, so the device computes step i+1 while
    the host blocks on step i's readback and the consumer handles the
    token (TPU010: a sync ordered before the next dispatch would idle
    the device for the whole host round-trip every step). The cost is
    one speculative dispatch when the consumer closes the stream early.
    """
    prefill_fn = prefill_fn or _prefill_fn(cfg)
    decode_fn = decode_fn or make_decode_fn(cfg)
    select = _select_fn()
    prompt = jnp.asarray(prompt, jnp.int32)
    b, l = prompt.shape
    if l >= cfg.max_len:
        raise ValueError(
            f"prompt length {l} leaves no room to generate within "
            f"max_len {cfg.max_len}"
        )
    max_new = min(max_new, cfg.max_len - l)
    sampled = temperature is not None and temperature > 0

    def pick(logits, step):
        if sampled:
            return select(logits, sampling_key(seed, step), temperature,
                          top_k)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    logits, (k_cache, v_cache) = prefill_fn(params, prompt)
    token = pick(logits, 0)
    for i in range(max_new):
        if i + 1 < max_new:
            # Dispatch step i+1 BEFORE materializing token i: the jitted
            # decode launches asynchronously, overlapping device compute
            # with the readback below and the consumer's handling.
            logits, k_cache, v_cache = decode_fn(
                params, k_cache, v_cache, token, jnp.int32(l + i)
            )
            next_token = pick(logits, i + 1)
        else:
            next_token = None
        # The single designed readback per step: this token goes out on
        # the wire now, and step i+1 is already running on-device.
        out = np.asarray(token)  # tpulint: disable=TPU010
        yield out
        token = next_token


@functools.lru_cache(maxsize=1)
def _select_fn():
    """One compiled sampler shared by every request (thresholds traced)."""
    return jax.jit(sample_token)


def generate_scan(params: Dict, prompt: jax.Array, max_new: int,
                  cfg: GptConfig, temperature=0.0, top_k=0,
                  seed=0) -> jax.Array:
    """Whole generation loop as one jit (lax.scan) → tokens [B, max_new].

    The throughput path, and the reference the streaming path is tested
    against (identical tokens ⇒ the cache math is right). Defaults are
    greedy; sampling follows the shared (seed, step) key schedule.
    """
    b, l = prompt.shape
    sampled = temperature is not None and float(temperature) > 0

    def pick(logits, step):
        if sampled:
            return sample_token(logits, sampling_key(seed, step),
                                temperature, top_k)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    logits, (k_cache, v_cache) = prefill(params, prompt, cfg)
    token0 = pick(logits, 0)

    def step(carry, i):
        token, kc, vc = carry
        logits, kc, vc = decode_step(params, kc, vc, token, l + i, cfg)
        return (pick(logits, i + 1), kc, vc), token

    (_, _, _), toks = lax.scan(
        step, (token0, k_cache, v_cache), jnp.arange(max_new)
    )
    return jnp.transpose(toks, (1, 0))  # [B, max_new]


# --------------------------------------------------------------------------- #
# serving model                                                               #
# --------------------------------------------------------------------------- #


def sampling_inputs(inputs):
    """(temperature, top_k, seed) from the optional request tensors.

    When sampling is requested (TEMPERATURE > 0) without an explicit
    SEED, a fresh random seed is drawn — otherwise every same-prompt
    request would return the identical "random" stream; an explicit SEED
    stays exactly reproducible.
    """
    temperature = 0.0
    if "TEMPERATURE" in inputs:
        temperature = float(np.asarray(inputs["TEMPERATURE"]).flatten()[0])
    top_k = 0
    if "TOP_K" in inputs:
        top_k = int(np.asarray(inputs["TOP_K"]).flatten()[0])
    if "SEED" in inputs:
        seed = int(np.asarray(inputs["SEED"]).flatten()[0])
    elif temperature > 0:
        import os as _os

        seed = int.from_bytes(_os.urandom(4), "little")
    else:
        seed = 0
    return temperature, top_k, seed


class GptModel(Model):
    """Decoupled LLM serving: one streamed response per generated token.

    Inputs: INPUT_IDS [B, L] int32 prompt; MAX_TOKENS [1] int32 (optional,
    default 16). Each response carries OUTPUT_IDS [B] — the next greedy
    token for every batch row — so a genai-perf-style client measures
    time-to-first-token on response 1 and inter-token latency on the gaps.
    """

    name = "gpt"
    platform = "jax"
    decoupled = True
    # The generation loop issues many device round-trips; keep it off the
    # aio event loop.
    blocking = True

    def __init__(self, cfg: Optional[GptConfig] = None, seed: int = 0,
                 use_flash_attention: bool = False,
                 checkpoint: Optional[str] = None):
        super().__init__()
        self.cfg = cfg or gpt_small()
        self.inputs = [
            TensorSpec("INPUT_IDS", "INT32", [-1, -1]),
            TensorSpec("MAX_TOKENS", "INT32", [1], optional=True),
            TensorSpec("TEMPERATURE", "FP32", [1], optional=True),
            TensorSpec("TOP_K", "INT32", [1], optional=True),
            TensorSpec("SEED", "INT64", [1], optional=True),
        ]
        self.outputs = [TensorSpec("OUTPUT_IDS", "INT32", [-1])]
        if checkpoint is not None:
            from tritonclient_tpu.models.checkpoint import load_params

            self._params = load_params(checkpoint)
        else:
            self._params = init_params(jax.random.PRNGKey(seed), self.cfg)
        attention_fn = None
        if use_flash_attention:
            from tritonclient_tpu.ops.flash_attention import flash_attention

            attention_fn = functools.partial(flash_attention, causal=True)
        self._prefill = jax.jit(functools.partial(
            prefill, cfg=self.cfg, attention_fn=attention_fn
        ))
        self._decode = make_decode_fn(self.cfg)
        # Parameter bytes on the device-memory ledger (per-device, from
        # the actual shardings).
        from tritonclient_tpu import _memscope

        _memscope.register_params(self.name, self._params)

    def infer(self, inputs, parameters=None) -> Iterator[dict]:
        prompt = np.asarray(inputs["INPUT_IDS"], dtype=np.int32)
        if prompt.ndim == 1:
            prompt = prompt.reshape(1, -1)
        if prompt.ndim != 2:
            raise ValueError(
                f"INPUT_IDS must be [B, L] (or [L]); got shape "
                f"{list(prompt.shape)}"
            )
        # Validated EAGERLY (not inside the lazy generator) so the caller
        # gets a clean per-request error, not a mid-stream shape blowup.
        if prompt.shape[1] >= self.cfg.max_len:
            raise ValueError(
                f"prompt length {prompt.shape[1]} must be < max_len "
                f"{self.cfg.max_len} to generate at least one token"
            )
        max_new = 16
        if "MAX_TOKENS" in inputs:
            max_new = int(np.asarray(inputs["MAX_TOKENS"]).flatten()[0])
        max_new = max(1, min(max_new, self.cfg.max_len - prompt.shape[1]))
        temperature, top_k, gen_seed = sampling_inputs(inputs)

        def gen():
            for token in generate_tokens(
                self._params, prompt, max_new, self.cfg,
                prefill_fn=self._prefill, decode_fn=self._decode,
                temperature=temperature, top_k=top_k, seed=gen_seed,
            ):
                yield {"OUTPUT_IDS": token}

        return gen()

    def warmup(self):
        list(generate_tokens(
            self._params, np.zeros((1, 8), np.int32), 2, self.cfg,
            prefill_fn=self._prefill, decode_fn=self._decode,
        ))
