"""Ragged paged attention over the flat KV pool: one Pallas TPU kernel.

The paged engine keeps keys and values in two pools
``[n_layers, n_blocks, block_size, H * Dh]`` (heads and head size flat on
the last axis) and a block table a request: entry *i* is the pool page that
holds its positions ``[i * block_size, (i + 1) * block_size)``. This kernel
attends a table's query rows to the pages that table really holds, where
they lie:

* the WHOLE pools come in and stay in HBM, with the layer's index as a
  scalar: nothing slices one layer's pages out (a pool-sized move), and no
  ``[..., H, Dh]`` view of a table exists outside fast memory;
* the block table, each table's number of live pages and the grid's own
  map (step -> table, chunk) are scalar prefetched. A grid step is one
  chunk of ``pages_per_chunk`` pages of one table, each page an operand of
  its own whose index map reads the table's entry, so the pipeline copies
  it from where it lies (a whole ``[block_size, H * Dh]`` row block: a
  manual copy cannot slice 1,600 columns out of rows the chip pads to
  1,664). The grid's length is traced: it is the number of chunks the
  bank's tables hold under their longest rows and no more, so a short
  context under a wide table costs what it holds and an idle slot (length
  1 on the scratch page) one step. In a table's last chunk an operand past
  the last live entry stays on the page it had, which is not copied again;
* heads are column groups of the flat axis, taken inside the kernel: a
  group is as many whole heads as fill 128 lanes (two of 64, one of 128,
  four of 32), so every slice of the page buffer starts on a lane tile at
  ``25 x 64`` and at ``16 x 128`` alike. A group's query rows are laid one
  head under the other, each zero outside its own head's columns, so one
  product with the group's key columns gives every head's scores, and the
  cross-head blocks of the value product are masked off at the end;
* keys and values enter the matrix unit as stored. A float32 left operand
  (the scaled query, the probabilities) goes in as three pool-typed parts
  that sum to it exactly, so each product with a bfloat16 page is exact and
  accumulates in float32: scores, the running maximum and sum, ``p`` and
  the accumulator are float32, as in the masked einsum this replaces
  (``models/gpt.py`` keeps that one for the contiguous cache). A float32
  pool takes float32 products at the highest precision.

One kernel serves decode (one query row a table) and the prefill chunk
(``rows_per_table`` rows a table, each with its own length: causality
among a chunk's rows is ``key position < row length``). Off the TPU it
runs under the Pallas interpreter; there is no second implementation.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_BIG = -1e30      # a masked score; finite, so no inf - inf
_LANES = 128
_ROW_TILE = 16        # query rows a table are padded to whole bf16 tiles


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _parts(x, dtype, n_parts: int):
    """``x`` [rows, n] (float32) as ``n_parts`` arrays of ``dtype`` that sum
    to it, one under the other: three bfloat16 parts carry all 24 bits of
    a float32 mantissa."""
    parts = []
    for _ in range(n_parts - 1):
        part = x.astype(dtype)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    parts.append(x.astype(dtype))
    return parts[0] if n_parts == 1 else jnp.concatenate(parts, axis=0)


def _dot_parts(a, b, contract_b: int, n_parts: int):
    """The float32 product of what ``a``'s stacked parts sum to with ``b``
    (``contract_b`` 0) or ``b``ᵀ (1). The parts go through the matrix unit
    in one pass, so ``b`` is loaded once."""
    out = lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST if a.dtype == jnp.float32
                   else None),
    )
    rows = a.shape[0] // n_parts
    total = out[(n_parts - 1) * rows:]
    for i in range(n_parts - 2, -1, -1):       # smallest part first
        total = total + out[i * rows:(i + 1) * rows]
    return total


def _kernel(layer_ref, page_ref, table_ref, chunk_ref, last_ref, q_ref,
            lens_ref, *refs, head_dim: int, heads_per_group: int,
            pages_per_chunk: int, n_parts: int):
    g, ppc = heads_per_group, pages_per_chunk
    k_pages, v_pages = refs[:ppc], refs[ppc:2 * ppc]
    o_ref, kbuf, vbuf, qpad, qparts, m_ref, l_ref, acc_ref = refs[2 * ppc:]
    step = pl.program_id(0)
    chunk = chunk_ref[step]
    bs = k_pages[0].shape[0]
    chunk_tokens, hd = kbuf.shape
    r, rp = q_ref.shape[0], qpad.shape[0]     # query rows a table; padded
    width = g * head_dim                      # columns of a group
    n_full, tail = hd // width, hd % width    # whole groups; a narrower last

    def each_group(do):
        """``do(c, columns, n_columns)`` for every column group: the whole
        ones in a loop (one traced body whatever their number: a step
        program is traced and lowered once an executable, 27 times a
        set-up), a narrower last one on its own."""
        if width % _LANES:      # a traced column offset must be lane tiles
            for c in range(n_full):
                do(c, slice(c * width, (c + 1) * width), width)
        else:
            def whole(c, carry):
                do(c, pl.ds(pl.multiple_of(c * width, width), width), width)
                return carry

            lax.fori_loop(0, n_full, whole, None)
        if tail:
            do(n_full, slice(n_full * width, hd), tail)

    def head_in_group(n_columns: int):
        # Which of a group's heads a column belongs to (g > 1 only: the
        # head size then divides 128, so both are powers of two).
        col = lax.broadcasted_iota(jnp.int32, (1, n_columns), 1)
        return lax.shift_right_logical(
            col, int(np.log2(head_dim))) & (g - 1)

    @pl.when(chunk == 0)
    def _():
        # The table's query rows, scaled, one copy a head of a group and
        # each zero outside its head's columns, in parts of the pool's type.
        x = q_ref[...].astype(jnp.float32) / np.sqrt(head_dim)
        if r < rp:
            qpad[...] = jnp.zeros_like(qpad)
            qpad[:r, :] = x
            x = qpad[...]
        if g > 1:
            x = jnp.concatenate(
                [jnp.where(head_in_group(hd) == j, x, 0.0)
                 for j in range(g)], axis=0)
        qparts[...] = _parts(x, qparts.dtype, n_parts)
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The chunk's pages side by side. A page past the table's last live
    # one holds an earlier live page (the plan's clamp): finite, and masked
    # below by its position.
    for i in range(ppc):
        kbuf[i * bs:(i + 1) * bs, :] = k_pages[i][...]
        vbuf[i * bs:(i + 1) * bs, :] = v_pages[i][...]
    position = chunk * chunk_tokens + lax.broadcasted_iota(
        jnp.int32, (1, chunk_tokens), 1)
    lens = lens_ref[...]                                   # [rp, 1]
    live = position < (lens if g == 1
                       else jnp.concatenate([lens] * g, axis=0))

    def attend(c, cols, _):
        s = _dot_parts(qparts[:, cols], kbuf[:, cols], 1, n_parts)
        s = jnp.where(live, s, _NEG_BIG)
        m_prev = m_ref[c]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[c] = alpha * l_ref[c] + p.sum(axis=1, keepdims=True)
        acc_ref[:, cols] = alpha * acc_ref[:, cols] + _dot_parts(
            _parts(p, vbuf.dtype, n_parts), vbuf[:, cols], 0, n_parts)
        m_ref[c] = m_new

    each_group(attend)

    @pl.when(last_ref[step] == 1)
    def _():
        def finish(c, cols, n_columns):
            out = acc_ref[:, cols] / l_ref[c]              # [g * rp, columns]
            if g > 1:
                # Row block j holds head j of the group: keep its columns.
                mine = head_in_group(n_columns)
                out = sum(jnp.where(mine == j, out[j * rp:(j + 1) * rp], 0.0)
                          for j in range(g))
            o_ref[:, cols] = out[:r]

        each_group(finish)


class PagePlan(NamedTuple):
    """What a bank's block tables and lengths say of the kernel's grid. It
    is the same for every layer of a step: make it once (``plan_pages``),
    outside the layer scan, whose body XLA does not hoist it from. The
    grid is the bank's live chunks, table after table, and no more; every
    map runs one entry past the longest grid there can be, and an entry
    past the grid's end repeats the last step's: the pipeline works out a
    step's block indices one step ahead, past the last step too (without
    the entry a two-table prefill halted the core on the chip)."""

    n_steps: jax.Array    # []: the bank's live chunks, the kernel's grid
    page_of: jax.Array    # [pages a chunk, steps]: grid step -> pool pages
    table_of: jax.Array   # [steps]: grid step -> table
    chunk_of: jax.Array   # [steps]: ... -> chunk of that table
    last_of: jax.Array    # [steps]: 1 on a table's last chunk
    lens: jax.Array       # [T, padded rows, 1]: each row's length


def _pages_per_chunk(block_size: int, n_ctx: int) -> int:
    return max(1, min(_LANES // block_size, n_ctx))


def plan_pages(btabs, lengths, *, rows_per_table: int = 1,
               block_size: int) -> PagePlan:
    """The plan for tables ``btabs`` [T, n_ctx] whose N = T *
    ``rows_per_table`` rows attend positions ``[0, lengths[n])``."""
    n_tables, n_ctx = btabs.shape
    r, rp = rows_per_table, _round_up(rows_per_table, _ROW_TILE)
    ppc = _pages_per_chunk(block_size, n_ctx)
    lens = jnp.clip(lengths.astype(jnp.int32), 1, n_ctx * block_size)
    lens = lens.reshape(n_tables, r)
    n_pages = -(-lens.max(axis=1) // block_size)   # entries under the longest
    n_chunks = -(-n_pages // ppc)
    ends = jnp.cumsum(n_chunks)
    steps = jnp.minimum(
        jnp.arange(n_tables * -(-n_ctx // ppc) + 1, dtype=jnp.int32),
        ends[-1] - 1)
    before = steps[:, None] >= ends[None, :]               # [steps, T]
    table_of = before.sum(axis=1).astype(jnp.int32)
    chunk_of = (steps - (before * n_chunks[None, :]).sum(axis=1)
                ).astype(jnp.int32)
    # Operand i of a step is its table's entry chunk * ppc + i. Past the
    # table's last live entry it stays on the last live one it had (or,
    # where it had none, on the table's last live page), so a dead page is
    # not copied again: the pipeline skips a block whose index did not
    # change. Never a dead entry: what it points at is anybody's.
    i = jnp.arange(ppc, dtype=jnp.int32)[:, None]          # [ppc, 1]
    live = n_pages[table_of]                               # [steps]
    last = live[None, :] - 1
    stay = jnp.where(last >= i, last - (last - i) % ppc, last)
    entry = jnp.minimum(chunk_of[None, :] * ppc + i, stay)
    page_of = btabs.astype(jnp.int32)[table_of[None, :], entry]
    last_of = ((chunk_of + 1) * ppc >= live).astype(jnp.int32)
    # Pad rows attend position 0 only.
    lens = jnp.pad(lens, ((0, 0), (0, rp - r)), constant_values=1)
    return PagePlan(ends[-1], page_of, table_of, chunk_of, last_of,
                    lens[..., None])


def _paged_attention(q, k_pool, v_pool, layer, plan: PagePlan, *,
                     rows_per_table: int):
    n, n_heads, head_dim = q.shape
    _, _, bs, hd = k_pool.shape
    r = rows_per_table
    n_tables = n // r
    # As many whole heads as fill a lane tile make one column group.
    g = _LANES // head_dim if _LANES % head_dim == 0 else 1
    g = max(1, min(g, n_heads))
    rp = plan.lens.shape[1]
    m_rows = g * rp
    ppc = plan.page_of.shape[0]
    n_groups = -(-hd // (g * head_dim))
    n_parts = 1 if k_pool.dtype == jnp.float32 else 3

    def page(i):
        return pl.BlockSpec(
            (None, None, bs, hd),
            lambda step, layer_ref, page_ref, *_: (
                layer_ref[0], page_ref[i, step], 0, 0))

    def table(rows, width):
        return pl.BlockSpec(
            (None, rows, width),
            lambda step, layer_ref, page_ref, table_ref, *_: (
                table_ref[step], 0, 0))

    pool_item = k_pool.dtype.itemsize
    hd_pad = _round_up(hd, _LANES)
    vmem = (6 * ppc * bs * hd_pad * pool_item        # pages (x 2) and buffers
            + n_parts * m_rows * hd_pad * pool_item        # query parts
            + (m_rows + rp) * hd_pad * 4           # accumulator, padded q
            + 2 * r * hd_pad * (q.dtype.itemsize + 4)      # q and out blocks
            + 12 * n_parts * m_rows * max(ppc * bs, _LANES) * 4)
    kernel = functools.partial(
        _kernel, head_dim=head_dim, heads_per_group=g, pages_per_chunk=ppc,
        n_parts=n_parts)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(plan.n_steps,),
            in_specs=([table(r, hd), table(rp, 1)]
                      + [page(i) for i in range(ppc)] * 2),
            out_specs=table(r, hd),
            scratch_shapes=[
                pltpu.VMEM((ppc * bs, hd), k_pool.dtype),
                pltpu.VMEM((ppc * bs, hd), v_pool.dtype),
                pltpu.VMEM((rp, hd), jnp.float32),
                pltpu.VMEM((n_parts * m_rows, hd), k_pool.dtype),
                pltpu.VMEM((n_groups, m_rows, 1), jnp.float32),
                pltpu.VMEM((n_groups, m_rows, 1), jnp.float32),
                pltpu.VMEM((m_rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tables, r, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(2 * vmem, 32 << 20), 100 << 20)),
        ),
        interpret=jax.default_backend() != "tpu",
        name="paged_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan.page_of, plan.table_of,
      plan.chunk_of, plan.last_of, q.reshape(n_tables, r, hd), plan.lens,
      *([k_pool] * ppc), *([v_pool] * ppc))
    return out.reshape(n, n_heads, head_dim)


def paged_attention(q, k_pool, v_pool, layer, btabs, lengths, *,
                    rows_per_table: int = 1, mesh=None, axis: str = "tp"):
    """Attention of ``q`` [N, H, Dh] over the pages its table holds →
    [N, H, Dh] float32.

    ``k_pool`` / ``v_pool`` are the whole pools ``[n_layers, n_blocks,
    block_size, H * Dh]`` and ``layer`` the (traced) index of the layer to
    read. ``btabs`` [T, n_ctx] int32 are the block tables, each attended by
    ``rows_per_table`` consecutive rows of ``q`` (N = T * rows_per_table);
    row *n* attends positions ``[0, lengths[n])`` of its table, at least
    one and at most the table's extent. Table entries past a table's
    longest row are never read. ``lengths`` is the [N] int32 array, or the
    ``PagePlan`` made from it (``plan_pages``) by a caller that attends
    the same tables in many layers.

    With a ``mesh`` that has ``axis``, the pools' flat axis and the heads
    of ``q`` are taken to be sharded on it, heads whole a shard: each
    shard runs the kernel on its own heads (the head size comes from
    ``q``'s shape, the number of heads from the shard's).
    """
    if (q.shape[0] != btabs.shape[0] * rows_per_table
            or k_pool.shape[3] != q.shape[1] * q.shape[2]
            or v_pool.shape != k_pool.shape):
        raise ValueError(
            f"q {q.shape} is not {btabs.shape[0]} tables x {rows_per_table} "
            f"rows over two pools {k_pool.shape}, {v_pool.shape}")
    plan = lengths if isinstance(lengths, PagePlan) else plan_pages(
        btabs, lengths, rows_per_table=rows_per_table,
        block_size=k_pool.shape[2])
    call = functools.partial(_paged_attention, rows_per_table=rows_per_table)
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return call(q, k_pool, v_pool, layer, plan)
    pool = P(None, None, None, axis)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(P(None, axis, None), pool, pool, P(),
                  PagePlan(*(P() for _ in plan))),
        out_specs=P(None, axis, None),
        axis_names={axis}, check_vma=False,
    )(q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), plan)
