"""The routed experts' product: one Pallas TPU kernel.

A routed layer gives each token to a few of many experts, and an expert is
three matrices (``W_gate``, ``W_up`` [d, f], ``W_down`` [f, d]) read for
however few rows chose it: the layer's time is the experts' bytes. This
kernel computes, for rows already sorted by expert,

    ``y[r] = W_down_e( silu(x[r] W_gate_e) * (x[r] W_up_e) )``,  e = r's expert

streaming each HIT expert's matrices through fast memory once a pass, the
hidden ``[rows, f]`` never leaving it:

* the WHOLE banks come in (``[G, d, f]`` and ``[G, f, d]``, G = every expert
  layer's held experts on one axis) and stay in HBM; a grid step's block of
  each is chosen by index from a scalar-prefetched plan, the layer's index
  (traced) folded into it. Nothing slices a layer's experts out of the
  stack, which would copy them (PERF.md, PR 27);
* the grid's first axis is the plan's VISITS: (row tile, expert) pairs in
  the sorted order, one for every expert that has rows in a tile. The rows
  come in tiles of ``tm``; consecutive visits of one tile keep its block
  (the output is accumulated in place), consecutive visits of one expert
  keep its matrices: neither is copied again. The axis is as long as the
  live visits and no longer (a traced length, as the paged-attention
  kernel's): an expert nobody chose is never read, and rows past the last
  live group (rows without a request, pairs whose expert another chip
  holds: the caller sorts them last) cost no product and no expert. Their
  output is never written: the caller keeps no such row;
* inside a visit the expert's rows ``[lo, hi)`` of the tile are computed in
  sub-tiles of ``sub`` rows, in a loop as long as the sub-tiles that hold
  one of them: a wide row tile costs fast memory, not products;
* where three whole matrices, double buffered, do not fit fast memory, the
  grid's second (inner) axis runs over tiles of ``f``: ``gate``/``up``
  column tiles and the matching row tile of ``W_down``, summed into the
  output block. Row tiles are then wide, because an expert whose rows lie
  in two tiles is streamed twice;
* operands enter the matrix unit as stored (bfloat16), sums are float32,
  ``silu(gate) * up`` is float32, the hidden is cast to the rows' type
  before ``W_down``, the output is float32. Float32 operands take products
  at the highest precision.

``tiling`` states how ``tm``, ``sub`` and the tile of ``f`` follow from
the shapes; there is no option. Off the TPU the kernel runs under the Pallas
interpreter; there is no second implementation.

Every executable that holds a Pallas kernel pays for tracing its body and
lowering it on every set-up, warm or cold (the compile cache's key needs the
lowered module), and an engine holds twenty-odd step programs of a handful
of row counts. So the caller (``models/mla_moe.py`` ``routed_experts``)
enters this through one jitted function, whose trace is kept by shape across
outer programs: the plan, the block specs and the kernel body are traced
once a distinct shape a process and lowered once a module. And the body and
the plan are written in plain ``lax`` operations: ``//``, ``jax.nn.silu``,
``jnp.where`` and ``jnp.cumsum`` are each a jitted function, and a function
inside a kernel body costs as much to lower as the rest of it (PERF.md §6,
PR 35: what each takes back of a warm set-up).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUB_ROWS = 128          # rows a product: the matrix unit's height
_TILE_ROWS = 512         # most rows a tile
_BANK_BYTES = 48 << 20   # most fast memory the matrices' blocks, x 2, take
_VMEM_BYTES = 100 << 20  # of the chip's 128 MiB


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class Tiling(NamedTuple):
    """How a call's static shapes are cut (``tiling``)."""

    tm: int        # rows a tile (a block of the rows and of the output)
    sub: int       # rows a product inside a visit
    tf: int        # columns of ``f`` a grid step; ``f`` itself where it fits
    visits: int    # the most (row tile, expert) visits a call can make


def tiling(n_rows: int, held: int, d: int, f: int, dtype) -> Tiling:
    """The one rule. ``sub``: 128 rows, or all of a call that has fewer (in
    whole tiles of the type: a decode step's 64 or 32 pairs are one
    sub-tile). ``tf``: ``f`` if three ``[d, f]`` blocks, double buffered,
    stay within ``_BANK_BYTES``, else the widest whole lane tiles dividing
    ``f`` that do. ``tm``: up to ``_TILE_ROWS`` rows, fewer where the
    rows' and the output's blocks (x 2) would not fit beside the
    matrices'."""
    item = jnp.dtype(dtype).itemsize
    sub = min(_SUB_ROWS, _round_up(n_rows, 32 // item))
    tf = f
    if 6 * d * f * item > _BANK_BYTES and f % _LANES == 0:
        tf = max(t for t in range(_LANES, f, _LANES)
                 if f % t == 0 and (6 * d * t * item <= _BANK_BYTES
                                    or t == _LANES))
    room = _VMEM_BYTES - 6 * d * tf * item - 4 * sub * (d + tf) * 4
    fit = max(room // (2 * d * (item + 4)) // sub, 1) * sub
    tm = min(_round_up(n_rows, sub), _TILE_ROWS, fit)
    return Tiling(tm, sub, tf, -(-n_rows // tm) + min(held, n_rows) - 1)


def _tiles_spanned(counts, ends, tm: int, div=np.floor_divide):
    """Row tiles of ``tm`` each group's rows lie in (0 for an empty group).
    NumPy arrays, or traced ones with ``div`` = ``lax.div``: what is divided
    is never negative (an empty group ends where it starts), so rounding
    towards zero is the floor, and it lowers to one operation where ``//``
    lowers to a function of ten."""
    last = ends - (counts > 0)             # a group's last row
    return (div(last, tm) - div(ends - counts, tm) + 1) * (counts > 0)


class VisitPlan(NamedTuple):
    """The grid of one call: visit -> (expert, row tile, the expert's rows
    in that tile). Every map runs one entry past the longest grid and an
    entry past the live visits repeats the last live one: the pipeline
    works out a step's block indices one step ahead, past the last step
    too."""

    n_visits: jax.Array   # []: the live visits, at least 1
    group_of: jax.Array   # [visits + 1]: visit -> index on the banks' axis
    tile_of: jax.Array    # [visits + 1]: ... -> row tile
    lo_of: jax.Array      # [visits + 1]: ... -> first row in the tile
    hi_of: jax.Array      # [visits + 1]: ... -> past its last row there


def plan_visits(counts, layer, cut: Tiling) -> VisitPlan:
    """The plan for rows sorted by expert, ``counts`` [held] rows each, the
    experts of ``layer`` (traced) in banks of ``held`` experts a layer. With
    no live row there is one visit of no rows (it empties tile 0)."""
    held, tm = counts.shape[0], cut.tm
    ends = lax.cumsum(counts)
    starts = ends - counts
    tiles = _tiles_spanned(counts, ends, tm, lax.div)
    upto = lax.cumsum(tiles)               # visits up to and with expert e
    v = jnp.minimum(jnp.arange(cut.visits + 1, dtype=jnp.int32),
                    jnp.maximum(upto[-1] - 1, 0))
    e = jnp.minimum((v[:, None] >= upto[None, :]).sum(axis=1), held - 1)
    start, end, first, before = jnp.stack(
        [starts, ends, lax.div(starts, tm), upto - tiles])[:, e]
    tile = first + v - before
    return VisitPlan(
        jnp.maximum(upto[-1], 1), (layer * held + e).astype(jnp.int32),
        tile.astype(jnp.int32),
        jnp.maximum(start - tile * tm, 0).astype(jnp.int32),
        jnp.minimum(end - tile * tm, tm).astype(jnp.int32))


def passes(counts, d: int, f: int, dtype) -> int:
    """How often calls with these per-expert row counts (``[..., held]``,
    NumPy; each last axis one call's) stream an expert's matrices. With
    ``f`` whole a grid step every hit expert is streamed once, however many
    row tiles its rows lie in (consecutive visits keep its blocks); with
    ``f`` in tiles, once a visit. The calls' row count is not asked: one
    of fewer rows than a tile has one tile, and any tile at least as tall
    counts alike. The engine's dispatch records hold this
    (``expert_passes``) beside the experts hit."""
    counts = np.asarray(counts)
    cut = tiling(_TILE_ROWS, counts.shape[-1], d, f, dtype)
    if cut.tf == f:
        return int((counts > 0).sum())
    return int(_tiles_spanned(counts, np.cumsum(counts, axis=-1),
                              cut.tm).sum())


def _kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, gate_ref, up_ref,
            down_ref, o_ref, *, sub: int):
    v, j = pl.program_id(0), pl.program_id(1)
    precision = (lax.Precision.HIGHEST if x_ref.dtype == jnp.float32
                 else None)

    @pl.when((j == 0) & ((v == 0) | (
        tile_ref[v] != tile_ref[jnp.maximum(v - 1, 0)])))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    lo, hi = lo_ref[v], hi_ref[v]

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=precision)

    def rows(i, carry):
        at = pl.ds(pl.multiple_of(i * sub, sub), sub)
        x = x_ref[at, :]
        gate = dot(x, gate_ref[...])
        hidden = (gate * lax.logistic(gate)
                  * dot(x, up_ref[...])).astype(x.dtype)
        out = dot(hidden, down_ref[...])
        row = i * sub + lax.broadcasted_iota(jnp.int32, out.shape, 0)
        # A row of another expert keeps what it has: + 0, exactly.
        o_ref[at, :] += lax.select((row >= lo) & (row < hi), out,
                                   jnp.zeros_like(out))
        return carry

    # lo and hi are never negative: rounding towards zero is the floor.
    lax.fori_loop(lax.div(lo, sub), lax.div(hi + (sub - 1), sub), rows, None)


def grouped_swiglu(rows, counts, w_gate, w_up, w_down, layer=0, *,
                   interpret: Optional[bool] = None):
    """``rows`` [P, d] sorted by expert, ``counts`` [held] of them each (the
    rows past their sum belong to no expert), the experts' matrices of
    every layer ``w_gate`` / ``w_up`` [G, d, f] and ``w_down`` [G, f, d]
    with G a multiple of ``held``, and whose ``layer`` it is → [P, d]
    float32, row r the SwiGLU of its expert. Rows past the live groups'
    last tile are NOT written: mask them. ``interpret``: under the Pallas
    interpreter; off the TPU unless said (a jitted caller that keeps its
    traces says, so that a trace for the chip never serves a call off it)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_rows, d = rows.shape
    f = w_gate.shape[2]
    cut = tiling(n_rows, counts.shape[0], d, f, rows.dtype)
    plan = plan_visits(counts.astype(jnp.int32), layer, cut)
    padded = _round_up(n_rows, cut.tm)
    if padded > n_rows:
        rows = jnp.pad(rows, ((0, padded - n_rows), (0, 0)))

    rows_tile = pl.BlockSpec(
        (cut.tm, d), lambda v, j, group_ref, tile_ref, *_: (tile_ref[v], 0))
    columns = pl.BlockSpec(          # of an expert's gate and up matrices
        (None, d, cut.tf), lambda v, j, group_ref, *_: (group_ref[v], 0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, sub=cut.sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(plan.n_visits, f // cut.tf),
            in_specs=[
                rows_tile, columns, columns,
                pl.BlockSpec((None, cut.tf, d),
                             lambda v, j, group_ref, *_: (group_ref[v], j, 0)),
            ],
            out_specs=rows_tile,
        ),
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="grouped_swiglu",
    )(plan.group_of, plan.tile_of, plan.lo_of, plan.hi_of, rows, w_gate,
      w_up, w_down)
    return out[:n_rows]
