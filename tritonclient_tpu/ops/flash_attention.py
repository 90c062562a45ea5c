"""Pallas flash attention: the fused TPU kernel for the hot op.

The plain dot_product_attention materializes the full [B, H, L, L] score
matrix in HBM; this kernel streams K/V tiles through VMEM with an online
softmax, so scores never leave the chip and memory stays O(L·D) per core —
the standard flash pattern mapped to the TPU grid model (MXU for the two
dot_generals, VMEM scratch carrying the running max/sum/accumulator across
the innermost K-tile dimension).

The backward pass is fused too: the forward emits the per-row logsumexp
(LSE), and two Pallas kernels recompute score tiles from (q, k, lse) to
produce dq and dk/dv without ever materializing the [L, L] score or
probability matrices — the same O(L·D) memory bound as the forward.

`return_lse=True` additionally returns the [B, L, H] logsumexp, which is
what sequence-parallel callers (ring attention) need to combine per-chunk
partial softmaxes; cotangents flowing into the LSE output are folded into
the backward kernels (they shift the per-row `delta` term), so ring-flash
is differentiable end to end.

Off-TPU (CPU tests, the virtual mesh) the kernels run in interpreter mode;
shapes the tiling cannot cover fall back to dot_product_attention, so
`flash_attention` is always safe to call.
"""

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tritonclient_tpu.ops.attention import dot_product_attention

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)
# Running max / sum / LSE live as (block_q, 128) tiles: f32 VMEM tiles are
# (8, 128)-granular, so a 128-wide broadcast column is the layout-safe shape
# (each row's scalar replicated across the lane dimension).
_STATS_LANES = 128


def _causal_mask(s, qi, ki, block_q, block_k):
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(q_pos >= k_pos, s, _NEG_BIG)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, causal: bool, scale: float, block_q: int, block_k: int,
                  num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: tiles entirely above the diagonal contribute nothing.
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                  # [Bk, D]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [Bq, Bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)

        m_prev = m_ref[:, :1]                              # [Bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # [Bq, Bk]
        corr = jnp.exp(m_prev - m_new)                     # [Bq, 1]
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.where(l_ref[:] == 0.0, 1.0,
                                                  l_ref[:]))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmg_ref,
                         dq_ref, acc_ref, *, causal: bool, scale: float,
                         block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _():
        qs = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [Bk, D]
        s = lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [Bq, Bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        reps = block_k // _STATS_LANES
        # Masked entries hold s=_NEG_BIG, so exp underflows to exactly 0 —
        # no separate probability re-mask is needed.
        p = jnp.exp(s - jnp.tile(lse_ref[0], (1, reps)))   # [Bq, Bk]
        do = do_ref[0].astype(jnp.float32)                 # [Bq, D]
        dp = lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [Bq, Bk]
        ds = p * (dp - jnp.tile(dmg_ref[0], (1, reps)))
        acc_ref[:] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k_blocks - 1)
    def _():
        dq_ref[0] = acc_ref[:] * scale


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmg_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                          scale: float, block_q: int, block_k: int,
                          num_q_blocks: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _():
        qs = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [Bq, Bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        reps = block_k // _STATS_LANES
        p = jnp.exp(s - jnp.tile(lse_ref[0], (1, reps)))
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [Bk, D]
        dp = lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - jnp.tile(dmg_ref[0], (1, reps)))
        # qs already carries the softmax scale, so dk = ds^T · (scale·q).
        dk_acc[:] += lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_q_blocks - 1)
    def _():
        dk_ref[0] = dk_acc[:]
        dv_ref[0] = dv_acc[:]


def _flat(x):
    """[B, L, H, D] -> [B*H, L, D]."""
    b, l, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)


def _unflat(x, b):
    """[B*H, L, D] -> [B, L, H, D]."""
    bh, l, d = x.shape
    return jnp.transpose(x.reshape(b, bh // b, l, d), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    """Primal: (o [B,L,H,D] in q.dtype, lse [B,L,H] f32)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    qf, kf, vf = _flat(q), _flat(k), _flat(v)
    num_q = lq // block_q
    num_k = lk // block_k
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(qf.shape[0], num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((qf.shape[0], lq, _STATS_LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),             # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    o = _unflat(out, b)
    # Stats are lane-replicated; column 0 is the per-row value.
    lse_rows = lse[:, :, 0].reshape(b, h, lq)
    return o, jnp.transpose(lse_rows, (0, 2, 1))


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash(q, k, v, causal, scale, block_q, block_k, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, residuals, cts):
    """Fused flash backward: two Pallas passes (dq; dk+dv), O(L·D) memory.

    The LSE cotangent folds into the per-row delta: for s = scale·q·kᵀ with
    lse = logsumexp(s), d(lse)/d(s_ij) = p_ij, so ds = p∘(dp − (Δ − g_lse))
    where Δ_i = Σ_j dO_ij·O_ij. With g_lse = 0 this is the standard flash
    backward (dv = pᵀ·dO, dq = scale·ds·k, dk = scale·dsᵀ·q).
    """
    q, k, v, o, lse = residuals
    go, glse = cts
    b, lq, h, d = q.shape
    lk = k.shape[1]
    qf, kf, vf = _flat(q), _flat(k), _flat(v)
    gof = _flat(go.astype(jnp.float32))
    of = _flat(o.astype(jnp.float32))
    lse_f = jnp.transpose(lse, (0, 2, 1)).reshape(-1, lq)          # [BH, Lq]
    glse_f = jnp.transpose(glse.astype(jnp.float32),
                           (0, 2, 1)).reshape(-1, lq)
    delta = jnp.sum(gof * of, axis=-1)                             # [BH, Lq]
    dmg = delta - glse_f
    # Stats are re-replicated to 128 lanes here because Mosaic reads them as
    # (block_q, 128) tiles; the residual stays the 128x-smaller [B, L, H]
    # form so it is the *held* memory between forward and backward (what
    # rematerialization trades against), and the lane replication is a
    # one-shot bandwidth cost paid only inside the backward.
    lse_b = jnp.broadcast_to(lse_f[..., None],
                             (*lse_f.shape, _STATS_LANES))
    dmg_b = jnp.broadcast_to(dmg[..., None], (*dmg.shape, _STATS_LANES))
    num_q = lq // block_q
    num_k = lk // block_k
    bh = qf.shape[0]

    q_spec_by = lambda qdim: pl.BlockSpec(
        (1, block_q, d), lambda bh_, a, b_, qdim=qdim: (
            bh_, (a if qdim == 1 else b_), 0))
    k_spec_by = lambda kdim: pl.BlockSpec(
        (1, block_k, d), lambda bh_, a, b_, kdim=kdim: (
            bh_, (a if kdim == 1 else b_), 0))
    stat_spec_by = lambda qdim: pl.BlockSpec(
        (1, block_q, _STATS_LANES), lambda bh_, a, b_, qdim=qdim: (
            bh_, (a if qdim == 1 else b_), 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k,
        ),
        grid=(bh, num_q, num_k),
        in_specs=[q_spec_by(1), k_spec_by(2), k_spec_by(2), q_spec_by(1),
                  stat_spec_by(1), stat_spec_by(1)],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh_, qi, ki: (bh_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, gof, lse_b, dmg_b)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q,
        ),
        grid=(bh, num_k, num_q),
        in_specs=[q_spec_by(2), k_spec_by(1), k_spec_by(1), q_spec_by(2),
                  stat_spec_by(2), stat_spec_by(2)],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, jnp.float32),
            jax.ShapeDtypeStruct(vf.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, gof, lse_b, dmg_b)

    return (_unflat(dq, b).astype(q.dtype), _unflat(dk, b).astype(k.dtype),
            _unflat(dv, b).astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _reference_with_lse(q, k, v, causal, scale):
    """Materializing fallback matching the kernel's (o, lse) contract."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32)
    )
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        keep = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(keep[None, None], s, _NEG_BIG)
    lse = jax.scipy.special.logsumexp(s, axis=-1)                  # [B,H,Lq]
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), jnp.transpose(lse, (0, 2, 1))


def flash_attention_path(
    q_shape: Tuple[int, ...],
    k_shape: Tuple[int, ...],
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> str:
    """Which implementation ``flash_attention`` runs for [B, L, H, D]
    operands of these shapes: ``"mosaic"`` (the kernel compiled for the
    TPU), ``"interpret"`` (the same kernel under the Pallas interpreter —
    what ``interpret=None`` resolves to on every backend but tpu), or
    ``"reference"`` (the materializing fallback for sequences that do not
    tile). Serving models report this so a run can say which one it
    measured.
    """
    lq, lk = q_shape[1], k_shape[1]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if (
        lq % block_q
        or lk % block_k
        # Blocks must respect the f32 (8, 128) sublane/lane tiling: block_q
        # is a sublane dim, block_k becomes the lane dim of the score tile
        # (and of the lane-replicated stats tiles, hence the 128 multiple).
        or block_q % 8
        or block_k % 128
        # Head dim is the lane dim of the q/k/v/acc tiles: Mosaic pads
        # lanes to 128, which we rely on for d in {8,16,...,120}; sub-8
        # or ragged head dims would need sublane-level padding too, so
        # fall back there instead of gambling on lowering.
        or q_shape[-1] % 8
        or (causal and block_q != block_k)
    ):
        return "reference"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return "interpret" if interpret else "mosaic"


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """q/k/v: [B, L, H, D] → [B, L, H, D]; same contract as
    dot_product_attention, computed tile-streamed on the TPU.

    Differentiable with a fused Pallas backward (score tiles recomputed from
    the saved logsumexp; the [L, L] matrices never materialize). With
    ``return_lse=True`` also returns the per-row logsumexp as [B, L, H]
    float32 — the combining statistic for sequence-parallel partial
    attention (ring attention) — and gradients flowing into it are exact.
    Falls back to the reference implementation whenever the sequence does
    not tile onto TPU-aligned blocks (the tiling, not the math, is the
    constraint).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    path = flash_attention_path(
        q.shape, k.shape, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    if path == "reference":
        if return_lse:
            return _reference_with_lse(q, k, v, causal, scale)
        return dot_product_attention(q, k, v, causal=causal, scale=scale)
    o, lse = _flash(q, k, v, causal, scale, min(block_q, q.shape[1]),
                    min(block_k, k.shape[1]), path == "interpret")
    return (o, lse) if return_lse else o
