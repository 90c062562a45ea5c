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
* heads are columns of the flat axis, told apart inside the kernel by
  laying query rows one head under the other, each zero outside its own
  head's columns: one product with the key columns then gives every such
  head's scores (a zeroed column adds exactly 0 to a float32 sum), and the
  cross-head blocks of the value product are masked off at the end. How
  many heads go through one product is what the kernel's TWO BODIES differ
  in, and the shapes decide (``straight_line``, the one rule; no option):

  - a table of FEW rows (decode: one query row a table, times the group)
    takes the STRAIGHT-LINE body: ALL the K/V heads' rows are stacked,
    ``H_kv x rows`` of them (at most ``_STRAIGHT_ROWS``: 25 -> 32 for 25 x
    64, 16 for 16 x 128, 64 for 8 K/V heads read by 8 query heads each),
    and a grid step is one score product over the whole flat axis, one
    softmax, one value product. With one row a table the rows that enter
    the matrix unit are padding either way, so this costs it what the
    column groups did (four times that at 64 rows) and drops everything
    else a group paid: on the chip a decode call fell from 155 / 198 us
    to 37 / 39 (gpt2-xl / cerebras contexts; PERF.md, PR 32), and the
    traced kernel holds no loop;
  - every other table (a prefill chunk's 32 rows, a 512-row tile) takes
    the LOOPED body: a column group is as many whole heads as fill 128
    lanes (two of 64, one of 128, four of 32), so every slice of the page
    buffer starts on a lane tile at ``25 x 64`` and at ``16 x 128`` alike,
    and the groups run one after the other in ONE traced loop body
    whatever their number: stacking every head's 32 rows would multiply
    the matrix unit's work by the number of heads, and unrolling the
    groups costs tracing and lowering in each of a set-up's 24 prefill
    executables;
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

Two things a call may add, and a call that adds neither lowers as it did
before they existed:

* GROUPED query heads: the pools hold ``H_kv`` heads and ``q`` has ``group``
  times as many, query head *i* reading K/V head ``i // group``. A K/V
  head's columns are one column group whatever reads it, so the ``group``
  query heads of a K/V head go in as ``group`` row blocks over the same
  columns: to the kernel a table then has ``group x rows`` rows and ``H_kv``
  heads, and nothing in it knows of grouping.
* a WINDOW: row *n* attends positions ``[lengths[n] - window, lengths[n])``
  only. The plan starts a table's chunks at the one that holds the earliest
  such position of its rows, so the grid holds the window's pages and not
  the context's; inside the kernel a key is live if it lies at or above the
  row's lower bound too. Entries of the table below that chunk are never
  read, so they may point at pages that hold later positions (a ring).

A table of many rows (a long prefill chunk, times the group) is cut into
TILES of at most ``_TILE_ROWS`` rows, each a table of its own to the plan
and the kernel: the buffers in fast memory keep one size, and a tile of
earlier rows visits the pages under its own rows alone.
"""

import functools
from typing import NamedTuple, Optional

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
_TILE_ROWS = 512      # most rows (group x positions) the kernel takes a table
_STRAIGHT_ROWS = 64   # most rows, every head's, the straight-line body takes


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


def straight_line(rows_per_table: int, q_heads: int, kv_heads: int) -> bool:
    """Whether a call whose tables have ``rows_per_table`` query positions
    each and ``q_heads`` query heads over ``kv_heads`` K/V heads (a
    shard's, under a mesh) takes the straight-line body (module docstring):
    every K/V head's rows, one head under the other, are few enough for one
    pass. A fact of the shapes and nothing else, whatever the head size:
    the kernel asks it when traced, the engine's dispatch records say what
    it answered."""
    group = q_heads // kv_heads
    rows = group * _tile_positions(rows_per_table, group)
    return kv_heads * rows <= _STRAIGHT_ROWS


def _kernel(layer_ref, page_ref, table_ref, chunk_ref, last_ref, q_ref,
            lens_ref, *refs, head_dim: int, heads_per_group: int,
            pages_per_chunk: int, n_parts: int, windowed: bool = False,
            straight: bool = False):
    g, ppc = heads_per_group, pages_per_chunk
    if windowed:
        lows_ref, *refs = refs
    k_pages, v_pages = refs[:ppc], refs[ppc:2 * ppc]
    o_ref, kbuf, vbuf, qparts, m_ref, l_ref, acc_ref, *qpad = refs[2 * ppc:]
    step = pl.program_id(0)
    chunk = chunk_ref[step]
    bs = k_pages[0].shape[0]
    chunk_tokens, hd = kbuf.shape
    r = q_ref.shape[0]                        # query rows a table

    if windowed:
        # A windowed table's first chunk is the one that holds its rows'
        # earliest key, not chunk 0: the step before is another table's.
        first = (step == 0) | (
            table_ref[step] != table_ref[jnp.maximum(step - 1, 0)])
    else:
        first = chunk == 0
    last = last_ref[step] == 1

    def start(x):
        """A table's first chunk: its query rows ``x`` as they enter the
        score product, in parts of the pool's type, and an empty sum."""
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

    def live_keys(copies: int):
        """[rows, chunk_tokens]: the keys of this chunk each row sees, the
        rows ``copies`` times one under the other."""
        def rows(ref):
            return (ref[...] if copies == 1
                    else jnp.concatenate([ref[...]] * copies, axis=0))

        live = position < rows(lens_ref)
        if windowed:
            # A row whose window lies wholly outside this chunk sees no
            # live key here: what that adds to its sum is multiplied by
            # exp(-1e30 - m) = 0 at the row's first live key, which every
            # row has (its own position), and after it a dead chunk adds
            # exp(-1e30 - m) = 0.
            live &= position >= rows(lows_ref)
        return live

    def attend(c, cols, live):
        """One online-softmax update of the rows' running maximum, sum and
        accumulator (entry ``c`` of the first two) with the chunk's keys
        and values at ``cols``."""
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

    if straight:
        # The straight-line body. EVERY head's rows one head under the
        # other (row block h is head h's ``r`` rows), each zero outside its
        # own head's columns: one product over the whole flat axis, one
        # softmax, one value product, and at the end row block h keeps head
        # h's columns. The lengths come in stacked the same way (the plan).
        # A row past the last head's block is all zero and kept by no
        # column.
        big = acc_ref.shape[0]                # the stacked rows, padded
        n_heads = hd // head_dim

        def own_columns():
            return (lax.broadcasted_iota(jnp.int32, (1, hd), 1) // head_dim
                    == lax.broadcasted_iota(jnp.int32, (big, 1), 0) // r)

        @pl.when(first)
        def _():
            x = q_ref[...].astype(jnp.float32) / np.sqrt(head_dim)
            if r == 1:
                x = jnp.broadcast_to(x, (big, hd))
            else:
                blocks = [x] * n_heads
                if big > n_heads * r:
                    blocks.append(
                        jnp.zeros((big - n_heads * r, hd), jnp.float32))
                x = jnp.concatenate(blocks, axis=0)
            start(jnp.where(own_columns(), x, 0.0))

        attend(0, slice(None), live_keys(1))

        @pl.when(last)
        def _():
            out = jnp.where(own_columns(), acc_ref[...] / l_ref[0], 0.0)
            o_ref[...] = (out.sum(axis=0, keepdims=True) if r == 1 else
                          sum(out[h * r:(h + 1) * r] for h in range(n_heads)))

        return

    # The looped body: the column groups one after the other.
    (qpad,) = qpad
    rp = qpad.shape[0]                        # the rows, padded
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

    @pl.when(first)
    def _():
        # The table's query rows, scaled, one copy a head of a group and
        # each zero outside its head's columns.
        x = q_ref[...].astype(jnp.float32) / np.sqrt(head_dim)
        if r < rp:
            qpad[...] = jnp.zeros_like(qpad)
            qpad[:r, :] = x
            x = qpad[...]
        if g > 1:
            x = jnp.concatenate(
                [jnp.where(head_in_group(hd) == j, x, 0.0)
                 for j in range(g)], axis=0)
        start(x)

    live = live_keys(g)
    each_group(lambda c, cols, _: attend(c, cols, live))

    @pl.when(last)
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
    is the same for every layer of a step (of one kind: a window layer's is
    its own): make it once (``plan_pages``), outside the layer scan, whose
    body XLA does not hoist it from. The grid is the bank's live chunks,
    table after table, and no more; every map runs one entry past the
    longest grid there can be, and an entry past the grid's end repeats the
    last step's: the pipeline works out a step's block indices one step
    ahead, past the last step too (without the entry a two-table prefill
    halted the core on the chip). Where a table's rows were cut into tiles,
    "table" below is a tile."""

    n_steps: jax.Array    # []: the bank's live chunks, the kernel's grid
    page_of: jax.Array    # [pages a chunk, steps]: grid step -> pool pages
    table_of: jax.Array   # [steps]: grid step -> table
    chunk_of: jax.Array   # [steps]: ... -> chunk of that table
    last_of: jax.Array    # [steps]: 1 on a table's last chunk
    lens: jax.Array       # [T, padded rows, 1]: row i % rows' length
    lows: Optional[jax.Array] = None   # windowed: ... and first live key


def _pages_per_chunk(block_size: int, n_ctx: int) -> int:
    return max(1, min(_LANES // block_size, n_ctx))


def _tile_positions(rows_per_table: int, group: int) -> int:
    """Positions a tile: the most that divide a table's rows and keep a
    tile's ``group x positions`` rows within ``_TILE_ROWS``."""
    most = max(_TILE_ROWS // group, 1)
    return next(t for t in range(min(rows_per_table, most), 0, -1)
                if rows_per_table % t == 0)


def plan_pages(btabs, lengths, *, rows_per_table: int = 1, block_size: int,
               group: int = 1, window: Optional[int] = None) -> PagePlan:
    """The plan for tables ``btabs`` [T, n_ctx] whose N = T *
    ``rows_per_table`` rows attend positions ``[0, lengths[n])``, or with a
    ``window`` its last ``window`` positions; each row stands for ``group``
    query heads a K/V head."""
    n_ctx = btabs.shape[1]
    ppc = _pages_per_chunk(block_size, n_ctx)
    lens = jnp.clip(lengths.astype(jnp.int32), 1, n_ctx * block_size)
    tile = _tile_positions(rows_per_table, group)
    if tile < rows_per_table:
        btabs = jnp.repeat(btabs, rows_per_table // tile, axis=0)
    n_tables = btabs.shape[0]
    lens = lens.reshape(n_tables, tile)
    n_pages = -(-lens.max(axis=1) // block_size)   # entries under the longest
    n_chunks = -(-n_pages // ppc)
    if window is not None:
        lows = jnp.maximum(lens - window, 0)
        first_chunk = lows.min(axis=1) // (block_size * ppc)
        n_chunks = n_chunks - first_chunk
    ends = jnp.cumsum(n_chunks)
    steps = jnp.minimum(
        jnp.arange(n_tables * -(-n_ctx // ppc) + 1, dtype=jnp.int32),
        ends[-1] - 1)
    before = steps[:, None] >= ends[None, :]               # [steps, T]
    table_of = before.sum(axis=1).astype(jnp.int32)
    chunk_of = (steps - (before * n_chunks[None, :]).sum(axis=1)
                ).astype(jnp.int32)
    if window is not None:
        chunk_of = chunk_of + first_chunk[table_of]
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
    # Each row's bounds, entry i those of row i % rows: the rows of a table
    # once a query head of its group, and then again from the top, as far
    # as the straight-line body can stack them. It finds row j of K/V head
    # h at h * rows + j whatever the number of heads (which the plan is not
    # told: under a mesh it is the shard's); the looped body reads the
    # first rows. An entry past the last real row repeats a real row's
    # bounds: a pad row attends what that row does, and is dropped.
    rows = group * tile
    padded = _round_up(max(rows, _STRAIGHT_ROWS), _ROW_TILE)

    def stacked(per_row):                                  # [T, tile]
        return jnp.tile(per_row, (1, -(-padded // tile)))[:, :padded, None]

    return PagePlan(ends[-1], page_of, table_of, chunk_of, last_of,
                    stacked(lens), None if window is None else stacked(lows))


def _paged_attention(q, k_pool, v_pool, layer, plan: PagePlan):
    n, q_heads, head_dim = q.shape
    _, _, bs, hd = k_pool.shape
    n_heads = hd // head_dim                  # K/V heads
    group = q_heads // n_heads                # query heads a K/V head
    n_tables = plan.lens.shape[0]             # tiles, where tables were cut
    tile = n // n_tables                      # positions a tile
    r = group * tile                          # rows a table, to the kernel
    windowed = plan.lows is not None
    straight = straight_line(tile, q_heads, n_heads)
    rp = _round_up(r, _ROW_TILE)
    if straight:
        # Every head's rows at once: one "group" of all the columns.
        g, n_groups = 1, 1
        m_rows = lens_rows = _round_up(n_heads * r, _ROW_TILE)
    else:
        # As many whole heads as fill a lane tile make one column group.
        g = _LANES // head_dim if _LANES % head_dim == 0 else 1
        g = max(1, min(g, n_heads))
        m_rows, lens_rows = g * rp, rp
        n_groups = -(-hd // (g * head_dim))
    ppc = plan.page_of.shape[0]
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

    if group > 1:
        # [tile, position, K/V head, query head of it, Dh] -> a row block a
        # query head of the group, over its K/V head's columns.
        q = q.reshape(n_tables, tile, n_heads, group, head_dim).transpose(
            0, 3, 1, 2, 4)
    pool_item = k_pool.dtype.itemsize
    hd_pad = _round_up(hd, _LANES)
    vmem = (6 * ppc * bs * hd_pad * pool_item        # pages (x 2) and buffers
            + n_parts * m_rows * hd_pad * pool_item        # query parts
            + (m_rows + rp) * hd_pad * 4           # accumulator, padded q
            + 2 * r * hd_pad * (q.dtype.itemsize + 4)      # q and out blocks
            + 12 * n_parts * m_rows * max(ppc * bs, _LANES) * 4)
    kernel = functools.partial(
        _kernel, head_dim=head_dim, heads_per_group=g, pages_per_chunk=ppc,
        n_parts=n_parts, straight=straight,
        **({"windowed": True} if windowed else {}))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(plan.n_steps,),
            in_specs=([table(r, hd)] + [table(lens_rows, 1)] * (1 + windowed)
                      + [page(i) for i in range(ppc)] * 2),
            out_specs=table(r, hd),
            scratch_shapes=[
                pltpu.VMEM((ppc * bs, hd), k_pool.dtype),
                pltpu.VMEM((ppc * bs, hd), v_pool.dtype),
                pltpu.VMEM((n_parts * m_rows, hd), k_pool.dtype),
                pltpu.VMEM((n_groups, m_rows, 1), jnp.float32),
                pltpu.VMEM((n_groups, m_rows, 1), jnp.float32),
                pltpu.VMEM((m_rows, hd), jnp.float32),
            ] + ([] if straight else [pltpu.VMEM((rp, hd), jnp.float32)]),
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
      *([plan.lows] if windowed else []),
      *([k_pool] * ppc), *([v_pool] * ppc))
    if group > 1:
        out = out.reshape(n_tables, group, tile, n_heads, head_dim).transpose(
            0, 2, 3, 1, 4)
    return out.reshape(n, q_heads, head_dim)


def paged_attention(q, k_pool, v_pool, layer, btabs, lengths, *,
                    rows_per_table: int = 1, window: Optional[int] = None,
                    mesh=None, axis: str = "tp"):
    """Attention of ``q`` [N, H, Dh] over the pages its table holds →
    [N, H, Dh] float32.

    ``k_pool`` / ``v_pool`` are the whole pools ``[n_layers, n_blocks,
    block_size, H_kv * Dh]`` and ``layer`` the (traced) index of the layer
    to read; ``H`` is ``H_kv`` or a whole multiple of it (grouped query
    heads: head *i* reads K/V head ``i // (H / H_kv)``). ``btabs`` [T,
    n_ctx] int32 are the block tables, each attended by ``rows_per_table``
    consecutive rows of ``q`` (N = T * rows_per_table); row *n* attends
    positions ``[0, lengths[n])`` of its table, at least one and at most the
    table's extent, or with a ``window`` the last ``window`` of them. Table
    entries past a table's longest row are never read, nor those below the
    chunk that holds its rows' earliest window. ``lengths`` is the [N] int32
    array, or the ``PagePlan`` made from it (``plan_pages``, given the same
    grouping and window) by a caller that attends the same tables in many
    layers.

    With a ``mesh`` that has ``axis``, the pools' flat axis and the heads
    of ``q`` are taken to be sharded on it, heads whole a shard: each
    shard runs the kernel on its own heads (the head size comes from
    ``q``'s shape, the number of heads from the shard's).
    """
    kv_heads, rest = divmod(k_pool.shape[3], q.shape[2])
    if (q.shape[0] != btabs.shape[0] * rows_per_table
            or rest or kv_heads == 0 or q.shape[1] % kv_heads
            or v_pool.shape != k_pool.shape):
        raise ValueError(
            f"q {q.shape} is not {btabs.shape[0]} tables x {rows_per_table} "
            f"rows over two pools {k_pool.shape}, {v_pool.shape}")
    plan = lengths if isinstance(lengths, PagePlan) else plan_pages(
        btabs, lengths, rows_per_table=rows_per_table,
        block_size=k_pool.shape[2], group=q.shape[1] // kv_heads,
        window=window)
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return _paged_attention(q, k_pool, v_pool, layer, plan)
    pool = P(None, None, None, axis)
    return jax.shard_map(
        _paged_attention, mesh=mesh,
        in_specs=(P(None, axis, None), pool, pool, P(),
                  PagePlan(*(None if f is None else P() for f in plan))),
        out_specs=P(None, axis, None),
        axis_names={axis}, check_vma=False,
    )(q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), plan)
