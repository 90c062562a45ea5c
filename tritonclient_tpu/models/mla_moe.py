"""A DeepSeek-V3-shaped decoder behind the paged engine: multi-head latent
attention (MLA) over one LATENT page pool, rotary positions (plain or
YaRN-scaled), RMSNorm, any number of leading dense SwiGLU layers and then
routed expert layers with a shared expert, on a residual path of one stream
or of several mixed by per-token maps (mHC, ``models/mhc.py``).
``JoyAI-LLM-Flash`` publishes this block with one stream, plain rotary and
one dense layer; ``Xing4.0-29B-A4B`` with four streams, YaRN and two.
Nothing here is specific to their sizes.

The layer, per token ``x`` (every norm RMSNorm):

  * attention: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> H heads of
    (nope + rope); ``x W_kva`` -> ``kv_lora_rank + rope``; ``c_kv =
    norm(first)``, ``k_r = RoPE(last)``, one for all heads; ``q_r =
    RoPE(q's rope part)``; ``[k_nope, v] = c_kv W_kvb`` per head; scores
    ``(q_nope . k_nope + q_r . k_r) / sqrt(nope + rope)``, causal softmax,
    ``. v``, ``W_o``. RoPE rotates interleaved pairs ``(2i, 2i + 1)``. With
    ``rope_scaling`` (YaRN) the pairs' frequencies are blended between
    kept and divided by ``factor`` (``rope_frequencies``) and the scores are
    also multiplied by ``yarn_mscale(factor, mscale_all_dim) ** 2``.
  * dense layers: ``W_down(silu(x W_gate) * x W_up)``.
  * expert layers: ``s = sigmoid(x W_g)`` in float32; the top k of ``s + b``
    are chosen (``b`` = ``e_score_correction_bias``; no group limit), their
    weights are ``s`` of the chosen (without ``b``) over their sum, times
    ``routed_scaling_factor``; ``y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)``.
    No token is dropped and there is no capacity factor.
  * the residual path: ``hc_mult`` 1 is ``x + F(norm(x))`` for attention and
    for the feed-forward. ``hc_mult`` n > 1 keeps n streams a token ``X [n,
    d]``, every stream the token's embedding at the start; each of a
    layer's two sublayers has maps of its own (leaves ``hc_att`` /
    ``hc_ffn``): ``h = H_pre . X`` goes into ``F(norm(h))`` and ``X' = H_res
    X + H_post^T F``, with ``H_res`` made doubly stochastic by
    ``hc_sinkhorn_iters`` Sinkhorn iterations (``mhc.py`` is the
    equations); the streams are summed before the final norm. 1 is a static
    Python branch: its programs are what they were before the field.

What the paged engine needs of it is ``MlaMoePaged`` (``gpt_engine.
PagedModel``): the pool is ONE array ``[n_layers, n_blocks, block_size,
kv_lora_rank + rope (+ zeros to whole lane tiles)]`` holding the normalised
``c_kv`` and the rotated ``k_r`` of every position, carried through both layer scans (the dense
layers, then the expert layers) and updated in place, as the GPT family's
two pools are. Decode attends it ABSORBED (``q_nope W_UK`` against the
latent itself, ``P . c_kv`` then ``W_UV``); a prefill chunk EXPANDS the
gathered latent to per-head keys and values (all its tables at once, or,
past ``_EXPAND_AT_ONCE`` positions, a table at a time). All the same
mathematics. A prefill chunk gathers the whole table it is given (the
engine hands it a context bucket); a decode step is given the whole table
and gathers the width its bank HOLDS: the tables' first page groups under
the longest live context, one of ``decode_widths`` (the table's halvings),
chosen on the device from the step's positions by ``decode_width`` (in a
fused dispatch every micro-step anew) as one branch of a ``lax.switch``
in ``_scan_layers_over_latent_pool``. A key past a row's position is masked
and adds an exact zero to the softmax's sums, so the narrower branch is the
whole table's result up to the order of a float32 sum.

The routed product is ONE Pallas kernel of the repo's own
(``ops/grouped_experts.py``) over the (token, expert) pairs sorted by
expert: it streams each expert the tokens HIT through fast memory once,
gate, up and down together with SwiGLU between them, so a decode step of 8
slots reads about 57 of a layer's 256 experts and not all of them, and the
hidden ``[pairs, f]`` never reaches HBM. Rows that carry no
request (idle slots, a chunk's padding) are given to no expert. Each step
returns, beside its tokens, the per-layer histogram of tokens per expert
(and, last, the pairs whose expert is held elsewhere: none here);
the engine's delivery thread reads it when stepscope is on, and for a
multi-stream configuration adds the streams a token and the (live row,
sublayer) pairs that passed the maps (``hc_streams``, ``hc_rows``).
"""

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tritonclient_tpu.models import mhc
from tritonclient_tpu.models._base import Model
from tritonclient_tpu.models.gpt_engine import (
    GenerationEngine,
    GptEngineModel,
    PagedModel,
    _sample_slots,
    wire_tensors,
)
from tritonclient_tpu.ops import grouped_experts

_HI = lax.Precision.HIGHEST


@dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type ``yarn``, under the published key names
    (``original_max_len`` = ``original_max_position_embeddings``)."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    d_model: int = 2048
    n_layers: int = 5              # the first ``n_dense_layers`` are dense
    n_dense_layers: int = 1        # any number, 0 included: a scan of their own
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168               # the dense layers' width
    n_experts: int = 256
    experts_per_token: int = 8
    d_expert: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32e6
    rope_scaling: Optional[YarnScaling] = None     # None: plain rotary
    rms_norm_eps: float = 1e-6
    max_len: int = 4096            # positions served (the block table's width)
    dtype: jnp.dtype = jnp.bfloat16
    # The residual path (``models/mhc.py``): 1 = one stream, ``x + F(norm(x))``,
    # a static branch that leaves the programs as they were; n > 1 = n
    # streams mixed around every sublayer by maps of its own.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def softmax_scale(self) -> float:
        """``1 / sqrt(nope + rope)``, times YaRN's ``mscale(factor,
        mscale_all_dim)`` squared where the positions are scaled."""
        scale = 1.0 / np.sqrt(self.qk_head_dim)
        ys = self.rope_scaling
        if ys is not None and ys.mscale_all_dim:
            scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
        return scale

    # The share of the router's experts this program holds (``routed_experts``):
    # all of them, from the first.
    @property
    def experts_held(self) -> int:
        return self.n_experts

    @property
    def first_expert(self) -> int:
        return 0

    @property
    def latent_dim(self) -> int:
        """What one position keeps in the cache: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A page row: the latent, padded with zeros to whole 128-lane
        tiles. ``[..., 576]`` gets a page-minor layout on the TPU and every
        step copies the pool into row-major and back; ``[..., 640]`` lies
        row-major as the scatter and the gather want it (the GPT pools'
        flat axis, for the same reason)."""
        return -(-self.latent_dim // 128) * 128


def mla_moe_tiny(max_len: int = 128) -> MlaMoeConfig:
    """Small config for tests and CPU runs (float32, so tolerances are tight)."""
    return MlaMoeConfig(
        vocab_size=256, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, d_ff=128, n_experts=8,
        experts_per_token=2, d_expert=32, max_len=max_len,
        dtype=jnp.float32)


def init_params(key: jax.Array, cfg: MlaMoeConfig) -> Dict:
    """Seeded weights in the parameter layout the steps read: ``dense`` and
    ``moe`` hold their layers stacked, attention leaves in both."""
    d, h = cfg.d_model, cfg.n_heads
    keys = iter(jax.random.split(key, 40))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    def attention(n):
        return {
            "norm1": jnp.ones((n, d), cfg.dtype),
            "wq_a": dense((n, d, cfg.q_lora_rank), d),
            "q_norm": jnp.ones((n, cfg.q_lora_rank), cfg.dtype),
            "wq_b": dense((n, cfg.q_lora_rank, h * cfg.qk_head_dim),
                          cfg.q_lora_rank),
            "wkv_a": dense((n, d, cfg.latent_dim), d),
            "kv_norm": jnp.ones((n, cfg.kv_lora_rank), cfg.dtype),
            "wkv_b": dense((n, cfg.kv_lora_rank,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                           cfg.kv_lora_rank),
            "wo": dense((n, h * cfg.v_head_dim, d), h * cfg.v_head_dim),
            "norm2": jnp.ones((n, d), cfg.dtype),
        }

    nd, nm, e = cfg.n_dense_layers, cfg.n_moe_layers, cfg.n_experts
    f, fe, fs = cfg.d_ff, cfg.d_expert, cfg.d_expert * cfg.n_shared_experts
    params = {
        "embed": {"tok": dense((cfg.vocab_size, d), d)},
        "dense": dict(
            attention(nd),
            w_gate=dense((nd, d, f), d), w_up=dense((nd, d, f), d),
            w_down=dense((nd, f, d), f)),
        "moe": dict(
            attention(nm),
            router=dense((nm, d, e), d),
            router_bias=0.05 * jax.random.normal(next(keys), (nm, e),
                                                 jnp.float32),
            w_gate=dense((nm, e, d, fe), d), w_up=dense((nm, e, d, fe), d),
            w_down=dense((nm, e, fe, d), fe),
            ws_gate=dense((nm, d, fs), d), ws_up=dense((nm, d, fs), d),
            ws_down=dense((nm, fs, d), fs)),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "head": dense((d, cfg.vocab_size), d),
    }
    if cfg.hc_mult > 1:
        # Keys of their own, so the other leaves are what one stream draws.
        hc = iter(jax.random.split(jax.random.fold_in(key, cfg.hc_mult), 4))
        for stack, n in (("dense", nd), ("moe", nm)):
            for sub in _HC_SUBLAYERS:
                params[stack][sub] = mhc.init_maps(
                    next(hc), n, cfg.hc_mult, d, cfg.dtype)
    return params


_HC_SUBLAYERS = ("hc_att", "hc_ffn")    # a layer's two sets of maps


# --------------------------------------------------------------------------- #
# the layer's parts                                                           #
# --------------------------------------------------------------------------- #


def _rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(cfg: MlaMoeConfig, dim: int):
    """(the ``dim / 2`` rotation frequencies, what cos and sin are
    multiplied by). Plain rotary: ``theta ** (-2i / dim)`` and 1. YaRN
    (``cfg.rope_scaling``): pair i keeps its frequency where it turns more
    than ``beta_fast`` times over the original positions, has it divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, and a
    linear blend between (pairs ``low``..``high``); cos and sin times
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    ys = cfg.rope_scaling
    if ys is None:
        return cfg.rope_theta ** (
            -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim), 1.0
    plain = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_turning(turns):        # the pair that turns ``turns`` times
        return dim * np.log(ys.original_max_len / (turns * 2 * np.pi)) / (
            2 * np.log(cfg.rope_theta))

    low = max(np.floor(pair_turning(ys.beta_fast)), 0)
    high = min(np.ceil(pair_turning(ys.beta_slow)), dim - 1)
    kept = 1.0 - np.clip((np.arange(dim // 2) - low)
                         / max(high - low, 1e-3), 0, 1)
    blended = (1.0 - kept) * plain / ys.factor + kept * plain
    return (jnp.asarray(blended, jnp.float32),
            float(yarn_mscale(ys.factor, ys.mscale)
                  / yarn_mscale(ys.factor, ys.mscale_all_dim)))


def _rope(x, positions, cfg: MlaMoeConfig):
    """Rotate the interleaved pairs ``(2i, 2i + 1)`` of the last axis by
    ``positions`` x ``rope_frequencies``; ``positions`` broadcasts against
    ``x`` without its last axis."""
    dim = x.shape[-1]
    inv_freq, magnitude = rope_frequencies(cfg, dim)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _dot(x, w):
    """``x @ w`` in the operands' type, accumulated in float32."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    return _dot(jax.nn.silu(_dot(x, w_gate)) * _dot(x, w_up), w_down)


def route(x, router, bias, cfg):
    """x [T, d] -> (experts [T, k] int32, weights [T, k] float32), over ALL
    the experts the router knows (its width), held here or not.

    The scores are float32 whatever the model's type (a bfloat16 score
    moves tokens between experts); the choice is by ``s + b``, the weight
    is ``s`` alone, normalised over the chosen and scaled. ``cfg`` is any
    family's config with ``experts_per_token`` and
    ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32), precision=_HI))
    _, experts = lax.top_k(scores + bias.astype(jnp.float32),
                           cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def routed_experts(x, experts, weights, live, banks, cfg, layer=0):
    """``sum_e w_e SwiGLU_e(x)`` over those of each token's chosen experts
    that this program HOLDS: one kernel (``ops/grouped_experts.py``) over
    the (token, expert) pairs sorted by expert.

    The share: ``cfg.experts_held`` experts from ``cfg.first_expert`` on, of
    the ``cfg.n_experts`` the router chooses among (one chip's share of an
    expert-parallel layer; a program that holds them all is the case
    ``first_expert`` 0, ``experts_held`` = ``n_experts``). A pair whose
    expert lives elsewhere adds nothing here: the chip that holds it adds
    its part, with the weight this chip would have given it (``weights``
    are normalised over all the chosen, held or not). Nothing stands in for
    the other chips or for the exchange.

    ``banks``: ``w_gate``/``w_up`` [G, d, f] and ``w_down`` [G, f, d] with
    G = layers * held, EVERY expert layer's held experts in one group axis,
    and ``layer`` (traced) says whose turn it is: the kernel picks expert
    ``layer * held + e`` by index and reads no other layer's. Slicing one
    layer's [held, d, f] out of the stack instead would copy it on its way
    into the product (measured on the v5e: 2.4 GB a layer, 29 ms of a 38 ms
    decode step).

    ``live`` [T] bool: a row that carries no request is given to no expert.
    Its pairs, like those of an expert held elsewhere, sort past every
    group, so the product neither computes them nor reads an expert for
    them (``grouped_swiglu`` leaves their rows unwritten: ``kept`` below).
    The sort, the gather and the scatter-back are plain ``jax.numpy``
    around the kernel; the routing weight, the sum over a token's k rows
    and then the cast are float32, as the kernel's sums are. Returns
    (y [T, d], counts [held + 1]: tokens per held expert, then the live
    pairs that fell elsewhere).

    All of it runs inside ONE jitted function, ``_routed_experts``: a step
    program's expert scan (and a fused decode's micro-steps) call this, an
    engine holds twenty-odd such programs of a handful of row counts, and
    every one of them traces what it calls on every set-up. The inner jit's
    trace is kept by shape across outer programs, so the sort, the kernel's
    plan, its block specs and its body are traced once a distinct shape a
    process and lowered once a module (PERF.md §6, PR 35). Nothing is static
    but the share's two integers and where the kernel runs (a fact of the
    process: a trace for the chip never serves a call off it).
    """
    return _routed_experts(x, experts, weights, live, banks, layer,
                           first=cfg.first_expert, held=cfg.experts_held,
                           interpret=jax.default_backend() != "tpu")


@functools.partial(jax.jit, static_argnames=("first", "held", "interpret"))
def _routed_experts(x, experts, weights, live, banks, layer, *, first: int,
                    held: int, interpret: bool):
    t, k = experts.shape
    local = experts - first
    mine = (local >= 0) & (local < held)
    flat = jnp.where(live[:, None] & mine, local, held).reshape(t * k)
    order = jnp.argsort(flat)                      # stable: pairs by expert
    counts = jnp.zeros((held,), jnp.int32).at[flat].add(1, mode="drop")
    elsewhere = jnp.sum(live[:, None] & ~mine, dtype=jnp.int32)
    y = grouped_experts.grouped_swiglu(
        x[order // k], counts, banks["w_gate"], banks["w_up"],
        banks["w_down"], layer, interpret=interpret)
    kept = (flat[order] < held)[:, None]           # rows past the groups
    y = jnp.where(kept, y * weights.reshape(t * k)[order][:, None], 0.0)
    # Back to token order: each token's k rows, summed.
    y = y[jnp.argsort(order)].reshape(t, k, -1).sum(axis=1)
    return y.astype(x.dtype), jnp.append(counts, elsewhere)


def routing_counters(histograms, cfg) -> dict:
    """A dispatch's routing counters (stepscope ``ROUTING_FIELDS``) from the
    histograms its expert layers returned (``routed_experts``' counts,
    ``[n_moe_layers, held + 1]`` or one such per micro-step); ``cfg`` is
    either routed family's config."""
    held, k = cfg.experts_held, cfg.experts_per_token
    counts = np.asarray(histograms).reshape(-1, cfg.n_moe_layers, held + 1)
    mine, elsewhere = counts[..., :held], counts[..., held]
    # Every expert layer routes the same rows: count them at the first.
    routed = int(mine[:, 0].sum() + elsewhere[:, 0].sum()) // k
    return {
        "routed_tokens": routed,
        "experts_hit": int((mine > 0).sum()),
        "experts_held": int(mine.size),
        "expert_load_max": int(mine.max()),
        "expert_load_mean": float(mine.mean()),
        "pairs_elsewhere": int(elsewhere.sum()),
        # How often the product streamed an expert's matrices: the experts
        # hit, each once, unless an expert's rows lay in two row tiles of
        # a product that takes ``f`` in tiles.
        "expert_passes": grouped_experts.passes(
            mine, cfg.d_model, cfg.d_expert, cfg.dtype),
    }


_EXPERT_BANKS = ("w_gate", "w_up", "w_down")


def expert_banks(moe: Dict) -> Dict:
    """The expert layers' stacked [L, E, ...] matrices as [L * E, ...]: a
    view, no copy."""
    return {k: moe[k].reshape((-1,) + moe[k].shape[2:])
            for k in _EXPERT_BANKS}


# The widest table (positions) a prefill chunk expands for all its tables
# at once; a wider one is expanded and attended a table at a time.
_EXPAND_AT_ONCE = 2048


def decode_widths(table_pages: int) -> Tuple[int, ...]:
    """The widths (table entries, ascending) a decode step's attention may
    take: the table's whole width and its halvings, at most four. A fact of
    the table alone: 256 to 4,096 positions of a 4,096-wide table, 512 to
    8,192 of an 8,192-wide one."""
    widths = [table_pages]
    while len(widths) < 5 and widths[-1] % 2 == 0:
        widths.append(widths[-1] // 2)
    return tuple(reversed(widths))


def decode_width(longest, table_pages: int, block_size: int):
    """Which of ``decode_widths`` (its index) a decode step takes for a bank
    whose longest live context is ``longest`` positions: the least that
    holds it. THE rule: the step applies it to the positions it is given (a
    traced scalar), the engine's dispatch record to the lengths it knows
    (an int)."""
    return sum(longest > pages * block_size
               for pages in decode_widths(table_pages)[:-1])


def _attend(q_nope, q_rope, table, mask, lp, cfg: MlaMoeConfig,
            absorbed: bool):
    """q_nope [T, R, H, nope], q_rope [T, R, H, rope] (rotated) against
    ``table`` [T, L, pool_width] (each table attended by its R rows: ``c_kv``,
    ``k_r``, then the row's zero padding, which ``q_rope`` is padded to
    meet); ``mask`` broadcasts against [T, R, H, L]. Returns [T, R, H * v].

    ``absorbed``: ``W_UK`` goes into the query and ``W_UV`` onto the
    output, so scores and values are taken against the latent itself and
    nothing of ``[L, H, ...]`` is made (decode: R = 1). Otherwise the
    latent is expanded to per-head keys and values first (a prefill
    chunk). The same mathematics either way.

    ``table`` need only reach past every row's last key: a masked score is
    ``finfo.min``, its probability an exact 0.0 once the row's maximum is
    subtracted (every row sees its own position), and it adds nothing to
    the softmax's sum or to ``P . c_kv``. So decode hands in the tables'
    first ``decode_widths`` entries over its bank's longest context and
    not their whole width (the caller's ``lax.switch``), and gets the whole
    width's result but for the order of the float32 sums.

    Expanded over more than ``_EXPAND_AT_ONCE`` positions, the tables go
    one after another (a fact of the shapes, no option): every table's
    ``[L, H, nope + v]`` keys and values and ``[R, H, L]`` float32 scores
    at once are 3.3 GB for 8 tables of 128 rows over 8,192 positions, and
    the compiler lays those scores out rows-minor, where the softmax's
    reduce-and-subtract took 70 ms a layer for what a table alone does in
    0.2 ms (v5e; PERF.md section 6, PR 33).
    """
    if not absorbed and table.shape[0] > 1 and (
            table.shape[1] > _EXPAND_AT_ONCE):
        return lax.map(
            lambda one: _attend(*(a[None] for a in one), lp, cfg, False)[0],
            (q_nope, q_rope, table,
             jnp.broadcast_to(mask, table.shape[:1] + mask.shape[1:])))
    dc, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    f32 = jnp.float32
    dtype = table.dtype
    wkv_b = lp["wkv_b"].reshape(dc, cfg.n_heads, dn + dv)
    scale = cfg.softmax_scale
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + (
        (0, table.shape[-1] - dc - q_rope.shape[-1]),))
    if absorbed:
        q_lat = jnp.einsum("trhd,chd->trhc", q_nope, wkv_b[..., :dn],
                           preferred_element_type=f32).astype(dtype)
        query = jnp.concatenate([q_lat, q_rope], axis=-1)
        scores = jnp.einsum("trhc,tlc->trhl", query, table,
                            preferred_element_type=f32)
    else:
        expanded = jnp.einsum("tlc,chx->tlhx", table[..., :dc], wkv_b,
                              preferred_element_type=f32).astype(dtype)
        scores = (jnp.einsum("trhd,tlhd->trhl", q_nope, expanded[..., :dn],
                             preferred_element_type=f32)
                  + jnp.einsum("trhd,tld->trhl", q_rope, table[..., dc:],
                               preferred_element_type=f32))
    scores = jnp.where(mask, scores * scale, jnp.finfo(f32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if absorbed:
        o_lat = jnp.einsum("trhl,tlc->trhc", probs, table[..., :dc],
                           preferred_element_type=f32).astype(dtype)
        out = jnp.einsum("trhc,chv->trhv", o_lat, wkv_b[..., dn:],
                         preferred_element_type=f32)
    else:
        out = jnp.einsum("trhl,tlhv->trhv", probs, expanded[..., dn:],
                         preferred_element_type=f32)
    return out.astype(dtype).reshape(out.shape[:2] + (cfg.n_heads * dv,))


def _scan_layers_over_latent_pool(params: Dict, x, pool, btabs, dest, off,
                                  positions, live, mask, cfg: MlaMoeConfig,
                                  absorbed: bool):
    """Every paged step's layers: the dense layers, then the expert layers,
    two scans with ``(h, pool)`` as the CARRY of both (the pool is never a
    scanned input or a stacked output: ``gpt_engine._scan_layers_over_pool``
    says what that costs; nor are the experts' matrices).

    x [N, d] with N = T * R rows: ``btabs`` [T, n_ctx] are the tables, each
    attended by R consecutive rows; ``dest``/``off``/``positions``/``live``
    are per row. A layer writes its N latent rows at ``(layer, page,
    offset)`` and gathers ``pool[layer, btabs]`` (``absorbed``, a decode
    step: the tables' first entries over the longest live context, one of
    ``decode_widths``). Returns (h, pool, tokens per expert [n_moe_layers,
    E + 1]).

    With ``cfg.hc_mult`` n > 1 the carried state is ``[N, n, d]``: every
    stream starts as the row's embedding, attention and the feed-forward
    are each wrapped ``pre -> F -> post`` by maps of their own
    (``models/mhc.py``, under the scopes ``mhc_pre`` / ``mhc_post``), and the streams are summed before they are
    returned, so the callers see ``[N, d]`` either way.
    """
    n_tables, n_ctx = btabs.shape
    n = x.shape[0]
    rows = n // n_tables
    h_, dn = cfg.n_heads, cfg.qk_nope_head_dim
    dc, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    if absorbed:
        # Decode attends the width its bank HOLDS: the least of the table's
        # few widths over the longest live context, chosen here, on the
        # device, once a step for all its layers. A slot with no request
        # sits on the scratch page wherever its position stands and widens
        # nothing; a bank with none takes the least width.
        longest = jnp.max(jnp.where(live, positions, 0)) + 1
        width = decode_width(longest, n_ctx, pool.shape[2])

    def attention(a, pool, lp, li):
        q = _dot(_rms_norm(_dot(a, lp["wq_a"]), lp["q_norm"], eps),
                 lp["wq_b"]).reshape(n, h_, cfg.qk_head_dim)
        kv = _dot(a, lp["wkv_a"])
        latent = jnp.concatenate(
            [_rms_norm(kv[:, :dc], lp["kv_norm"], eps),
             _rope(kv[:, dc:], positions, cfg),
             jnp.zeros((n, cfg.pool_width - cfg.latent_dim), kv.dtype)],
            axis=-1)
        # One scatter at (layer, page, offset), then only the tables' pages
        # are read: [T, n_ctx, bs, width] -> [T, L, width].
        pool = pool.at[li, dest, off].set(latent.astype(pool.dtype))

        def attend(pool, pages):    # over the tables' first ``pages`` entries
            table = pool[li, btabs[:, :pages]].reshape(
                n_tables, -1, cfg.pool_width)
            return _attend(
                q[..., :dn].reshape(n_tables, rows, h_, dn),
                _rope(q[..., dn:], positions[:, None], cfg).reshape(
                    n_tables, rows, h_, -1),
                table, mask[..., :table.shape[1]], lp, cfg, absorbed)

        if absorbed:
            out = lax.switch(width, [functools.partial(attend, pages=w)
                                     for w in decode_widths(n_ctx)], pool)
        else:
            out = attend(pool, n_ctx)
        return _dot(out.reshape(n, -1), lp["wo"]), pool

    def plain_layer(ffn, carry, xs):
        h, pool = carry
        lp, li = xs
        y, pool = attention(_rms_norm(h, lp["norm1"], eps), pool, lp, li)
        h = h + y
        y, counts = ffn(_rms_norm(h, lp["norm2"], eps), lp, li)
        return (h + y, pool), counts

    def layer_of_streams(ffn, carry, xs):
        state, pool = carry
        lp, li = xs
        h, back = mhc.pre(state, lp["hc_att"], cfg)
        y, pool = attention(_rms_norm(h, lp["norm1"], eps), pool, lp, li)
        state = mhc.post(state, y, back)
        h, back = mhc.pre(state, lp["hc_ffn"], cfg)
        y, counts = ffn(_rms_norm(h, lp["norm2"], eps), lp, li)
        return (mhc.post(state, y, back), pool), counts

    def dense_ffn(x, lp, li):
        return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None

    nd, moe = cfg.n_dense_layers, params["moe"]
    if cfg.hc_mult > 1:
        layer = layer_of_streams
        x = jnp.broadcast_to(x[:, None, :], (n, cfg.hc_mult, x.shape[-1]))
    else:
        layer = plain_layer
    carry, _ = lax.scan(functools.partial(layer, dense_ffn), (x, pool),
                        (params["dense"], jnp.arange(nd)))
    # The experts' matrices are not scanned: the scan would slice (copy) a
    # layer's out of the stack; the grouped product takes the whole stack
    # and the layer's index (``routed_experts``).
    banks = expert_banks(moe)

    def moe_ffn(x, lp, li):
        experts, weights = route(x, lp["router"], lp["router_bias"], cfg)
        y, counts = routed_experts(x, experts, weights, live, banks, cfg,
                                   li - nd)
        return (y + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
                counts)

    (x, pool), counts = lax.scan(
        functools.partial(layer, moe_ffn), carry,
        ({k: v for k, v in moe.items() if k not in _EXPERT_BANKS},
         nd + jnp.arange(cfg.n_moe_layers)))
    if cfg.hc_mult > 1:
        x = x.astype(jnp.float32).sum(axis=1).astype(x.dtype)
    return x, pool, counts


def _head(params: Dict, x, cfg: MlaMoeConfig):
    x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def _pick(logits, seeds, steps, temps, topks):
    # Greedy-only banks (the default) skip the sampler's full-vocab sort.
    return lax.cond(
        jnp.any(temps > 0),
        lambda: _sample_slots(logits, seeds, steps, temps, topks),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32),
    )


# --------------------------------------------------------------------------- #
# the three paged steps                                                       #
# --------------------------------------------------------------------------- #


def _decode_step_latent(params: Dict, pool, btabs, tokens, pos, seeds, steps,
                        temps, topks, cfg: MlaMoeConfig, block_size: int):
    """One step for the whole slot bank against the latent pool; the
    arguments are ``gpt_engine._decode_step_paged``'s with one pool for two.
    A slot whose table starts at the scratch page (0) holds no request: it
    still advances, its latent lands on the scratch page, and it is routed
    to no expert. Returns (next tokens [S], pool, tokens per expert
    [n_moe_layers, E + 1])."""
    s_count, max_blocks = btabs.shape
    l_eff = max_blocks * block_size
    x = params["embed"]["tok"][tokens]
    blk = jnp.minimum(pos // block_size, max_blocks - 1)
    dest = btabs[jnp.arange(s_count), blk]
    mask = (jnp.arange(l_eff)[None, :] <= pos[:, None])[:, None, None, :]
    x, pool, counts = _scan_layers_over_latent_pool(
        params, x, pool, btabs, dest, pos % block_size, pos,
        btabs[:, 0] > 0, mask, cfg, absorbed=True)
    nxt = _pick(_head(params, x, cfg), seeds, steps, temps, topks)
    return nxt, pool, counts


def _decode_multi_step_latent(params: Dict, pool, btabs, tokens, pos, seeds,
                              steps, temps, topks, cfg: MlaMoeConfig,
                              block_size: int, n_steps: int):
    """``n_steps`` micro-steps in one dispatch: a scan over the single step
    (``gpt_engine._decode_multi_step_paged``). The histogram comes back per
    micro-step, [n_steps, n_moe_layers, E + 1]."""

    def one(carry, _):
        tokens, pos, steps, pool = carry
        nxt, pool, counts = _decode_step_latent(
            params, pool, btabs, tokens, pos, seeds, steps, temps, topks,
            cfg, block_size)
        return (nxt, pos + 1, steps + 1, pool), (nxt, counts)

    (tokens, pos, steps, pool), (toks, counts) = lax.scan(
        one, (tokens, pos, steps, pool), None, length=n_steps)
    return toks, tokens, pos, steps, pool, counts


def _prefill_chunk_latent(params: Dict, pool, chunks, btabs, starts,
                          n_valids, seeds, temps, topks, cfg: MlaMoeConfig,
                          block_size: int):
    """One prompt chunk for K prefilling slots in a single dispatch, their
    latents written into the pages of ``btabs`` [K, n_ctx]; the arguments
    and the causality-by-position are ``gpt_engine._prefill_chunk_paged``'s.
    Pad rows and pad lanes write to the scratch page and reach no expert.
    Returns (first tokens [K], pool, tokens per expert [n_moe_layers, E + 1])."""
    kk, c = chunks.shape
    n_ctx = btabs.shape[1]
    l_eff = n_ctx * block_size
    rows = jnp.arange(c, dtype=jnp.int32)
    positions = starts[:, None] + rows[None, :]                # [K, C]
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    valid = (rows[None, :] < n_valids[:, None]) & (btabs[:, :1] > 0)
    blk = jnp.minimum(safe_pos // block_size, n_ctx - 1)
    dest = jnp.where(valid, jnp.take_along_axis(btabs, blk, axis=1), 0)
    mask = (jnp.arange(l_eff)[None, None, :]
            <= positions[:, :, None])[:, :, None, :]
    x = params["embed"]["tok"][chunks].reshape(kk * c, cfg.d_model)
    x, pool, counts = _scan_layers_over_latent_pool(
        params, x, pool, btabs, dest.reshape(kk * c),
        (safe_pos % block_size).reshape(kk * c), safe_pos.reshape(kk * c),
        valid.reshape(kk * c), mask, cfg, absorbed=False)
    last = jnp.take_along_axis(
        x.reshape(kk, c, cfg.d_model),
        (n_valids - 1).astype(jnp.int32)[:, None, None], axis=1)[:, 0]
    firsts = _pick(_head(params, last, cfg), seeds, jnp.zeros_like(seeds),
                   temps, topks)
    return firsts, pool, counts


class MlaMoePaged(PagedModel):
    """This family as the engine's scheduler sees it."""

    def __init__(self, cfg: MlaMoeConfig):
        self.cfg = cfg

    def pool_arrays(self, n_blocks: int, block_size: int):
        cfg = self.cfg
        return (jnp.zeros((cfg.n_layers, n_blocks, block_size,
                           cfg.pool_width), cfg.dtype),)

    def block_bytes(self, block_size: int) -> int:
        cfg = self.cfg
        return (cfg.n_layers * block_size * cfg.pool_width
                * np.dtype(cfg.dtype).itemsize)

    def pages_gathered(self, longest: int, table_pages: int,
                       block_size: int) -> int:
        return decode_widths(table_pages)[
            decode_width(longest, table_pages, block_size)]

    def shard(self, mesh, params):
        raise NotImplementedError(
            "the MLA/MoE family is served on one device: it has no "
            "partition rules yet (expert placement and the latent pool "
            "under tp are ROADMAP B2/B5); pass mesh=None")

    # The jitted wrappers look the step functions up in this module when
    # traced, as the GPT family's do, and carry names of their own onto the
    # device trace: jit_mla_moe_decode_step, jit_mla_moe_decode_fused_<n>,
    # jit_mla_moe_prefill_chunk.

    def decode_step(self, block_size: int):
        cfg = self.cfg

        def mla_moe_decode_step(params, pool, btabs, tokens, pos, seeds,
                                steps, temps, topks):
            return _decode_step_latent(
                params, pool, btabs, tokens, pos, seeds, steps, temps,
                topks, cfg=cfg, block_size=block_size)

        return mla_moe_decode_step

    def decode_fused(self, block_size: int, n_steps: int):
        cfg = self.cfg

        def decode_fused(params, pool, btabs, tokens, pos, seeds, steps,
                         temps, topks):
            return _decode_multi_step_latent(
                params, pool, btabs, tokens, pos, seeds, steps, temps,
                topks, cfg=cfg, block_size=block_size, n_steps=n_steps)

        decode_fused.__name__ = f"mla_moe_decode_fused_{n_steps}"
        decode_fused.__qualname__ = decode_fused.__name__
        return decode_fused

    def prefill_chunk(self, block_size: int):
        cfg = self.cfg

        def mla_moe_prefill_chunk(params, pool, chunks, btabs, starts,
                                  n_valids, seeds, temps, topks):
            return _prefill_chunk_latent(
                params, pool, chunks, btabs, starts, n_valids, seeds, temps,
                topks, cfg=cfg, block_size=block_size)

        return mla_moe_prefill_chunk

    def routing(self, extras) -> Optional[dict]:
        """A dispatch's routing counters from the histogram it returned:
        read on the engine's delivery thread, for stepscope's dispatch
        record."""
        cfg = self.cfg
        counters = routing_counters(extras[0], cfg)
        if cfg.hc_mult > 1:
            # Every live row passes two sublayers' maps a layer.
            counters["hc_streams"] = cfg.hc_mult
            counters["hc_rows"] = (counters["routed_tokens"] * 2
                                   * cfg.n_layers)
        return counters


class MlaMoeEngineModel(GptEngineModel):
    """The family served through the continuous-batching engine, under the
    GPT engine model's wire contract (INPUT_IDS [1, L], optional MAX_TOKENS,
    TEMPERATURE, TOP_K, SEED; one OUTPUT_IDS response a token)."""

    name = "mla_moe_engine"

    def __init__(self, cfg: Optional[MlaMoeConfig] = None, seed: int = 0,
                 params: Optional[Dict] = None, max_slots: int = 8,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 prefill_chunk: int = 32):
        Model.__init__(self)
        self.cfg = cfg or mla_moe_tiny()
        self.inputs, self.outputs = wire_tensors()
        if params is None:
            params = init_params(jax.random.PRNGKey(seed), self.cfg)
        self.engine = GenerationEngine(
            MlaMoePaged(self.cfg), params, max_slots=max_slots,
            scope_name=self.name, block_size=block_size, n_blocks=n_blocks,
            prefill_chunk=prefill_chunk)
