"""Compute/collective overlap for tensor-parallel projections.

The gpt PARTITION_RULES row-shard the attention output projection (``wo``)
and the FFN down projection (``w_out``) on the tp axis, which forces one
all-reduce per projection: ``y = psum(x_local @ w_local)``. Under plain
GSPMD that psum is a single launch whose full ``[n, d_out]`` payload sits
on the step critical path between the two matmuls of adjacent blocks.

``row_parallel_proj`` restructures the projection the way
Triton-distributed tiles it (arxiv 2504.19442): split the *output* dim
into C chunks and issue ``matmul(chunk i) → psum(chunk i) → matmul(chunk
i+1) → …`` inside a partial-manual ``jax.shard_map`` region. Because each
chunk's all-reduce is issued before the next chunk's matmul, XLA's async
collectives (all-reduce start/done pairs on TPU) can run the wire transfer
of chunk *i* under the MXU work of chunk *i+1* — only the trailing chunk's
collective is structurally exposed. Chunking the output dim (not the
contraction dim) keeps total all-reduce bytes identical to the unchunked
projection and keeps per-element accumulation order unchanged, so decode
token streams are unaffected.

The stepscope side: ``_stepscope.expected_tp_collectives(n_layers, tp,
overlap_chunks)`` counts the extra launches and
``_stepscope.expected_overlap_split`` says how many of them hide; the
engine charges calibrated exposed/hidden µs per step from those counts
(see ``GenerationEngine``). ``calibrate_collective_us`` measures the
per-launch all-reduce cost once on the live mesh.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tritonclient_tpu import _stepscope


def pick_chunks(d_out: int, tp: int, chunks: int) -> int:
    """Clamp a requested chunk count to what the geometry supports: each
    chunk must be a whole slice of the output dim. Returns 1 (no
    chunking) when tp is trivial or nothing divides."""
    if tp <= 1 or chunks <= 1:
        return 1
    chunks = int(chunks)
    while chunks > 1 and d_out % chunks != 0:
        chunks -= 1
    return max(chunks, 1)


# tpulint: hot-path
def row_parallel_proj(x, w, b, *, mesh: Mesh, axis: str = "tp",
                      chunks: int = 2, note: bool = True):
    """``x @ w + b`` with ``w`` row-sharded on ``axis``, issued as
    ``chunks`` matmul+psum pairs so the all-reduce on chunk *i* can
    execute under the matmul on chunk *i+1*.

    ``x`` is ``[n, d_in]`` with ``d_in`` sharded on ``axis`` (the
    activation produced by the preceding column-parallel matmul), ``w`` is
    ``[d_in, d_out]`` sharded on dim 0, ``b`` is replicated. The result is
    replicated. ``note=False`` skips the trace-time stepscope notes for
    callers (the engine) that charge structural per-step counts instead.
    """
    tp = mesh.shape.get(axis, 1)
    d_out = w.shape[-1]
    n_chunks = pick_chunks(d_out, tp, chunks)
    if n_chunks <= 1 and tp <= 1:
        return x @ w + b

    csz = d_out // n_chunks

    def body(xl, wl, bl):
        parts = []
        for c in range(n_chunks):
            part = xl @ lax.slice_in_dim(wl, c * csz, (c + 1) * csz, axis=1)
            if note:
                _stepscope.note_collective(
                    "psum", nbytes=int(part.size) * part.dtype.itemsize
                )
            # Issued before the next chunk's matmul: on TPU the async
            # all-reduce runs under it; only the last chunk is exposed.
            parts.append(lax.psum(part, axis))
        out = parts[0] if n_chunks == 1 else jnp.concatenate(parts, axis=-1)
        return out + bl

    # Partial-manual: only ``axis`` is manual, other mesh axes stay GSPMD.
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None), P(None)),
        out_specs=P(None, None),
        axis_names={axis}, check_vma=False,
    )(x, w, b)


def make_row_parallel_proj(mesh: Mesh, axis: str = "tp", chunks: int = 2,
                           note: bool = True):
    """Bind ``row_parallel_proj`` to a mesh as the ``proj_fn(x, w, b)``
    closure the gpt decode layer accepts."""

    def proj(x, w, b):
        return row_parallel_proj(x, w, b, mesh=mesh, axis=axis,
                                 chunks=chunks, note=note)

    return proj


# Run-once calibration (the engine caches the result): the jit build is
# per-mesh by design and the block_until_ready calls ARE the measurement.
# tpulint: disable=TPU010
def calibrate_collective_us(mesh: Mesh, shape, dtype=jnp.float32,
                            axis: str = "tp", reps: int = 20) -> float:
    """Median wall µs of one all-reduce of ``shape``/``dtype`` over the
    mesh's ``axis`` — the per-launch cost the engine multiplies by the
    structural counts of ``expected_overlap_split``. Returns 0.0 when the
    axis is trivial or the measurement fails (attribution degrades to
    counts-only, never breaks serving)."""
    if mesh.shape.get(axis, 1) <= 1:
        return 0.0
    try:
        fn = jax.jit(jax.shard_map(
            lambda t: lax.psum(t, axis),
            mesh=mesh,
            in_specs=P(None),
            out_specs=P(None),
            axis_names={axis}, check_vma=False,
        ))
        probe = jnp.zeros(shape, dtype)
        jax.block_until_ready(fn(probe))  # compile outside the clock
        samples = []
        for _ in range(max(int(reps), 3)):
            t0 = time.perf_counter_ns()
            jax.block_until_ready(fn(probe))
            samples.append((time.perf_counter_ns() - t0) / 1000.0)
        samples.sort()
        return samples[len(samples) // 2]
    except Exception:
        return 0.0


def overlap_chunks_from_env(default: int = 2) -> int:
    """Requested chunk count for the engine's overlap projections
    (``TPU_ENGINE_OVERLAP_CHUNKS``), before geometry clamping."""
    import os

    try:
        return max(int(os.environ.get("TPU_ENGINE_OVERLAP_CHUNKS",
                                      str(default))), 1)
    except ValueError:
        return default


def overlap_enabled_from_env(default: bool = True) -> bool:
    """``TPU_ENGINE_OVERLAP`` gate (default on; the projection only
    engages when the mesh actually has a tp axis > 1)."""
    import os

    raw = os.environ.get("TPU_ENGINE_OVERLAP", "").strip().lower()
    if raw in ("", None):
        return default
    return raw not in ("0", "off", "false", "no")
