"""Ring attention: sequence-parallel attention over a mesh axis.

Each device holds a sequence chunk of Q/K/V; K/V blocks rotate around the
ring via `lax.ppermute` while a flash-style online softmax accumulates the
output, so attention over the full sequence never materializes on one chip
and the sp-axis collectives ride ICI. Runs inside a partial-manual
`jax.shard_map` (only the sp axis is manual; dp/tp stay under GSPMD).

The reference has no analog (client SDK, SURVEY.md §2.5); this is the
long-context plane the TPU framework needs for sequence lengths beyond one
chip's HBM.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tritonclient_tpu import _stepscope


def _noted_ppermute(x, axis_name, perm):
    """lax.ppermute + a stepscope collective note. The note fires at JAX
    trace time (once per compiled call site, on the thread whose step
    triggered compilation) — cheap attribution, not an execution count."""
    _stepscope.note_collective(
        "ppermute", nbytes=int(x.size) * x.dtype.itemsize
    )
    return lax.ppermute(x, axis_name, perm)


_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)


def sequence_shard_map(body, mesh: Mesh, sp_axis: str):
    """Partial-manual shard_map over the sp axis for [B, L, H, D] q/k/v.

    Shared scaffolding of the sequence-parallel attention variants: only
    the sp axis is manual; dp/tp stay under GSPMD.
    """
    spec = P(None, sp_axis, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={sp_axis},
        check_vma=False,
    )


def _ring_body_flash(q, k, v, *, axis_name: str, axis_size: int,
                     causal: bool, scale: float):
    """Flash variant: each hop runs the fused Pallas kernel on the local
    Q chunk against the visiting K/V chunk (``return_lse=True``), and the
    per-hop partials combine with the standard two-way logsumexp merge.
    Gradients flow through the kernel's LSE cotangent path, ppermute, and
    the combine, so ring-flash is differentiable end to end.
    """
    from tritonclient_tpu.ops.flash_attention import flash_attention

    my_idx = lax.axis_index(axis_name)
    b, lc, h, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def full_hop(k_cur, v_cur):
        return flash_attention(q, k_cur, v_cur, causal=False, scale=scale,
                               return_lse=True)

    def diag_hop(k_cur, v_cur):
        # j == my_idx: the visiting chunk is this device's own K/V, so the
        # in-chunk causal mask is exactly the aligned q_pos >= k_pos mask.
        return flash_attention(q, k_cur, v_cur, causal=True, scale=scale,
                               return_lse=True)

    def skip_hop(k_cur, v_cur):
        # Entirely above the diagonal: weight exp(_NEG_BIG) == 0 in the merge.
        return (jnp.zeros_like(q), jnp.full((b, lc, h), _NEG_BIG,
                                            jnp.float32))

    def step(carry, i):
        o_acc, lse_acc, k_cur, v_cur = carry
        # After i hops each device holds the chunk that started (my_idx - i).
        j = (my_idx - i) % axis_size
        if causal:
            idx = jnp.where(j < my_idx, 0, jnp.where(j == my_idx, 1, 2))
            o_j, lse_j = lax.switch(idx, [full_hop, diag_hop, skip_hop],
                                    k_cur, v_cur)
        else:
            o_j, lse_j = full_hop(k_cur, v_cur)
        m = jnp.maximum(lse_acc, lse_j)
        w_acc = jnp.exp(lse_acc - m)
        w_j = jnp.exp(lse_j - m)
        denom = w_acc + w_j
        o_acc = (o_acc * w_acc[..., None]
                 + o_j.astype(jnp.float32) * w_j[..., None]) / denom[..., None]
        lse_acc = m + jnp.log(denom)
        k_next = _noted_ppermute(k_cur, axis_name, perm)
        v_next = _noted_ppermute(v_cur, axis_name, perm)
        return (o_acc, lse_acc, k_next, v_next), None

    o0 = jnp.zeros((b, lc, h, d), jnp.float32)
    lse0 = jnp.full((b, lc, h), _NEG_BIG, jnp.float32)
    (o, _, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                               jnp.arange(axis_size))
    return o.astype(q.dtype)


def _ring_body(q, k, v, *, axis_name: str, axis_size: int, causal: bool,
               scale: float):
    """Manual-mode body: q/k/v are the local [B, Lc, H, D] chunks."""
    my_idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    qf = q.astype(jnp.float32) * scale

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        # After i hops each device holds the chunk that started (my_idx - i).
        j = (my_idx - i) % axis_size
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my_idx * lq + jnp.arange(lq)
            k_pos = j * lk + jnp.arange(lk)
            keep = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(keep[None, None], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(keep[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        k_next = _noted_ppermute(k_cur, axis_name, perm)
        v_next = _noted_ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros((b, h, lq, d), jnp.float32)
    m0 = jnp.full((b, h, lq), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3))  # [B, Lq, H, D]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    sp_axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "reference",
) -> jax.Array:
    """Attention over [B, L, H, D] tensors whose L dim is sharded on sp_axis.

    Other mesh axes (dp on B, tp on H) stay automatic — GSPMD shards them as
    annotated by the caller. With sp size 1 this degrades to plain attention.
    ``impl='flash'`` runs the fused Pallas kernel per hop (online softmax
    inside the chunk, logsumexp merge across chunks) instead of the
    materializing per-chunk einsum — the combination for long context, where
    neither the full sequence nor a chunk's score matrix fits HBM.

    On the TPU ``impl='flash'`` does not lower under JAX 0.9.0 once a chunk
    tiles onto the kernel: the shard_map here is partial-manual (dp/tp stay
    automatic) and Mosaic raises "Mosaic kernels cannot be automatically
    partitioned" (four v5e chips, PR 21; heads replicated or on tp alike).
    It runs interpreted off-TPU; on chips use ``impl='reference'``.
    """
    if impl not in ("reference", "flash"):
        raise ValueError("impl must be 'reference' or 'flash'")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    sp_size = mesh.shape.get(sp_axis, 1)
    if sp_size == 1:
        if impl == "flash":
            from tritonclient_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=causal, scale=scale)
        from tritonclient_tpu.ops.attention import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal, scale=scale)
    body = functools.partial(
        _ring_body_flash if impl == "flash" else _ring_body,
        axis_name=sp_axis,
        axis_size=sp_size,
        causal=causal,
        scale=scale,
    )
    return sequence_shard_map(body, mesh, sp_axis)(q, k, v)
