"""Continuous batching for LLM serving: concurrent generations share steps.

`GptModel` runs one generation loop per request; at concurrency N that is
N separate single-token dispatches per token. This engine runs ONE
jit-compiled decode step over a fixed bank of S slots — every active
request advances one token per step, requests join at token boundaries
(the continuous/in-flight batching scheduler of modern LLM servers) and
leave when finished, and a freed slot is immediately refilled from the
admission queue.

KV memory is PAGED (vLLM block tables / Ragged Paged Attention geometry):
a fixed pool of ``[n_layers, n_blocks, block_size, H * Dh]`` pages plus a
per-slot block table ``[S, max_len // block_size]``. A request reserves
``ceil((prompt + max_new) / block_size)`` pages at admission (deadlock-
free: decode never allocates mid-flight) and returns them the moment it
finishes, sheds, or cancels — memory is block-granular, not
slot-lifetime-granular, so a long-context straggler no longer pins
``max_len`` KV for every cohabitant.

TPU-first mechanics:
  * static shapes everywhere: the pool, the block tables, and the slot
    vectors never change shape, so the decode step compiles exactly
    once; block-table indices are TRACED operands — paging costs a
    table look-up, never a recompile;
  * per-slot cache writes are batched scatters into pages
    (``.at[dest_block, offset]``); the GPT family's attention reads the
    pages under each slot's position where they lie, through one Pallas
    kernel over the whole pool (``ops/paged_attention.py``), in the
    float32 mathematics of the old contiguous bank's masked einsum
    (token-for-token against it, tested);
  * block 0 is the reserved SCRATCH page: idle and still-prefilling
    slots keep an all-zeros block-table row, routing their garbage
    decode writes there — in a paged layout a stray write into a
    reallocated page would corrupt another request's KV, which the old
    contiguous bank never had to worry about;
  * prompts stream into their pages through a fixed-size CHUNKED
    prefill interleaved with decode steps (one compiled chunk shape
    replaces the power-of-two bucket family), so a long prompt no
    longer stalls the decode loop for everyone else;
  * completed FULL prompt pages register in a hash-keyed prefix cache
    (tritonclient_tpu._kvcache): a shared system prompt resolves to
    block-table entries instead of recompute — shared pages are always
    full, so decode never writes into them and no copy-on-write is
    needed;
  * caches are donated through both jits — the pool lives in HBM
    in-place for the server's lifetime;
  * one host readback per STEP ([S] int32) serves every active stream —
    token egress cost is amortized across the batch.

Greedy decoding matches `gpt.generate_tokens` token-for-token (tested),
so continuous batching changes scheduling, never results.
"""

import os
import queue
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tritonclient_tpu import _kvcache, _memscope, _stepscope, sanitize
from tritonclient_tpu.models._base import Model, TensorSpec
from tritonclient_tpu.models.gpt import (
    GptConfig,
    _decode_layer,
    _head,
    gpt_small,
    init_params,
    sample_token,
    sampling_inputs,
    sampling_key,
)
from tritonclient_tpu.ops.paged_attention import (
    paged_attention,
    plan_pages,
    straight_line,
)
from tritonclient_tpu.protocol._literals import (
    PREFIX_EVENT_HIT,
    PREFIX_EVENT_MISS,
)


def _block_pool_arrays(cfg: GptConfig, n_blocks: int, block_size: int):
    """The K and V pools, each ``[n_layers, n_blocks, block_size, H * Dh]``.

    The last axis is heads and head size FLAT. For ``[..., 25, 64]`` the
    TPU's default layout puts the page axis minor (``{1,4,3,2,0}``), and a
    step would re-lay every layer's pages out on the way in and on the
    way out; ``[..., 1600]`` gets the row-major ``{3,2,1,0}`` the scatter
    and the gather want, padded 4% instead of 25%.
    """
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_heads * cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def _scan_layers_over_pool(params: Dict, x, k_pool, v_pool, btabs, dest, off,
                           rows_per_table: int, lengths, cfg: GptConfig,
                           mesh=None):
    """The layer scan of every paged step: ``(h, k_pool, v_pool)`` is the
    CARRY and the scanned inputs are the layer's parameters and its index.

    The pools are never an ``xs`` or a ``ys`` of the scan. A scanned pool
    has each layer's pages sliced out, written back into a second stacked
    pool and that stack copied onto the donated buffer: about four passes
    over the pool a dispatch, for a write of one position a slot. Carried,
    what a step does to the pool compiles to one in-place scatter at
    ``(layer, page, offset)``, and the attention kernel
    (``ops.paged_attention``) takes the whole pools with the layer's
    index and reads the pages the tables hold, where they lie.

    ``dest``/``off`` [N] are the page and offset of each of the N rows'
    new K/V; ``btabs`` [T, n_ctx] are the block tables, each attended by
    ``rows_per_table`` consecutive rows (N = T * rows_per_table: 1 for
    decode, the chunk length for prefill); row n attends positions
    ``[0, lengths[n])`` of its table, and no page past a table's longest
    row is read. Under a ``mesh`` the pools' flat axis is on ``tp`` and
    each shard attends its own heads.
    """
    # What the tables and lengths say of the kernel's grid is the same in
    # every layer: made here once, not in the scan's body.
    plan = plan_pages(btabs, lengths, rows_per_table=rows_per_table,
                      block_size=k_pool.shape[2])

    def layer(carry, xs):
        h, k_pool, v_pool = carry
        lp, li = xs

        def write(pool, rows):
            # One scatter at (layer, page, offset), rows [N, H * Dh].
            return pool.at[li, dest, off].set(
                rows.reshape(rows.shape[0], -1).astype(pool.dtype))

        h, (k_pool, v_pool) = _decode_layer(
            h, lp, k_pool, v_pool, cfg,
            lambda kc, vc, k, v: (write(kc, k), write(vc, v)),
            lambda q, kc, vc: paged_attention(
                q, kc, vc, li, btabs, plan,
                rows_per_table=rows_per_table, mesh=mesh))
        return (h, k_pool, v_pool), None

    (x, k_pool, v_pool), _ = lax.scan(
        layer, (x, k_pool, v_pool),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, k_pool, v_pool


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two ≥ n, capped — the shape-bucketing rule for
    both the chunk-prefill lane count and its context extent (compile
    count stays logarithmic in max_slots × max_blocks)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _advance_slot_clocks(pos, steps):
    """Whole-bank slot-clock advance for the unfused decode branch.

    Jitted with ``donate_argnums=(0, 1)``: both inputs are dead the
    moment the step dispatches, so on TPU the buffers are recycled in
    place instead of allocating two fresh device vectors every step
    (TPU015 donation discipline). The CPU backend ignores donation, so
    token streams are unchanged on the test tier.
    """
    return pos + 1, steps + 1


def _sample_slots(logits, seeds, steps, temps, topks):
    """Per-slot sampling on the shared (seed, step) key schedule —
    vmapped so every slot keeps its own request's settings and key
    stream, bit-identical to the single-request path's sampler."""

    def one(lg, seed, step, temp, tk):
        return sample_token(lg[None], sampling_key(seed, step), temp, tk)[0]

    return jax.vmap(one)(logits, seeds, steps, temps, topks)


def _decode_step_paged(params: Dict, k_pool, v_pool, btabs, tokens, pos,
                       seeds, steps, temps, topks, cfg: GptConfig,
                       block_size: int, mesh=None):
    """One step for the whole slot bank against the paged pool.

    ``btabs`` [S, max_blocks] int32 maps each slot's logical block index
    to a pool page (0 = the scratch page). tokens/pos/seeds/steps/topks
    [S] int32, temps [S] f32 → (next sampled tokens [S] int32, pools).
    Sampling happens on device — logits never leave the chip. Every slot
    advances; idle slots carry an all-scratch table, so their garbage
    K/V lands on the scratch page instead of a page some OTHER request
    now owns. The pools are ``[n_layers, n_blocks, bs, H * Dh]`` and ride
    the layer scan as its carry (``_scan_layers_over_pool``): a layer
    scatters its S new rows at ``(layer, page, offset)`` and the
    paged-attention kernel reads, for each slot, the pages under its
    position and no more of its table: float32 scores, softmax and
    accumulation over the K/V as stored, the mathematics of the contiguous
    bank's masked einsum (token for token against it, tested).
    """
    s_count = tokens.shape[0]
    max_blocks = btabs.shape[1]
    x = params["embed"]["tok"][tokens] + params["embed"]["pos"][pos]  # [S, d]
    slot_ids = jnp.arange(s_count)
    # Surplus pipeline steps can push pos past the reserved region; the
    # clamp keeps the (dropped-anyway) write inside the slot's own row.
    blk = jnp.minimum(pos // block_size, max_blocks - 1)
    off = pos % block_size
    dest = btabs[slot_ids, blk]                              # [S] page ids
    x, k_pool, v_pool = _scan_layers_over_pool(
        params, x, k_pool, v_pool, btabs, dest, off, 1, pos + 1, cfg, mesh)
    logits = _head(params, x, cfg)
    # Greedy-only banks (the default) skip the sampler's full-vocab sort.
    nxt = lax.cond(
        jnp.any(temps > 0),
        lambda: _sample_slots(logits, seeds, steps, temps, topks),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32),
    )
    return nxt, k_pool, v_pool


def _decode_multi_step_paged(params: Dict, k_pool, v_pool, btabs, tokens,
                             pos, seeds, steps, temps, topks,
                             cfg: GptConfig, block_size: int, n_steps: int,
                             mesh=None):
    """``n_steps`` decode micro-steps in ONE dispatch: a ``lax.scan`` over
    the exact single-step body, returning the ``[n_steps, S]`` token
    block plus the advanced carry.

    This is the fused form of the dispatch pipeline: one host dispatch
    and ONE readback amortize over ``n_steps`` tokens, so per-step host
    work (trace-cache lookup, argument donation, executable launch, the
    delivery hand-off) leaves the step critical path — the term that
    dominates tp scaling on dispatch-bound hosts. Because the scan body
    IS ``_decode_step_paged``, token streams are identical to ``n_steps``
    lockstep dispatches (same HLO per micro-step, same sampling key
    schedule); pool donation stays safe because the whole fused window is
    one XLA program. The scheduler only fuses when every active request
    still needs ≥ ``n_steps`` tokens, no slot is prefilling, and the
    admission queue is empty — surplus beyond a request's budget is
    bounded and dropped by the delivery pairs like any pipeline surplus.
    """

    def one(carry, _):
        tokens, pos, steps, k_pool, v_pool = carry
        nxt, k_pool, v_pool = _decode_step_paged(
            params, k_pool, v_pool, btabs, tokens, pos, seeds, steps,
            temps, topks, cfg, block_size, mesh=mesh,
        )
        return (nxt, pos + 1, steps + 1, k_pool, v_pool), nxt

    (tokens, pos, steps, k_pool, v_pool), toks = lax.scan(
        one, (tokens, pos, steps, k_pool, v_pool), None, length=n_steps
    )
    return toks, tokens, pos, steps, k_pool, v_pool


def _prefill_chunk_paged(params: Dict, k_pool, v_pool, chunks, btabs,
                         starts, n_valids, seeds, temps, topks,
                         cfg: GptConfig, block_size: int, mesh=None):
    """One fixed-size prompt chunk for K prefilling slots in a SINGLE
    dispatch, K/V written into the pages of ``btabs`` [K, n_ctx] int32.

    chunks [K, C] int32 (each lane zero-padded past its ``n_valids``);
    ``starts`` [K] is the absolute position of each lane's chunk[0] (a
    prefix-cache hit starts past its shared pages). Batching across
    slots is the TTFT-under-churn term: batched decode steps finish
    batchmates together, their clients resubmit together, and K serial
    chunk dispatches at one loop top would put k×chunk-time in front of
    every admission in the burst. Rows attend the pages' already-written
    positions AND each other causally by their lengths — all rows are
    written first, then the kernel reads them back, so intra-chunk
    causality falls out of ``position < my position + 1``. Pad rows (and
    pad lanes) route their writes to the scratch page and attend position
    0 alone, so no page a lane has not written yet is read; lanes read
    only their own table, so cross-lane isolation is structural, not
    masked. ``n_ctx`` (the traced table width) is the caller-bucketed
    context extent — no row attends a key past its lane's last valid
    position, so truncating the table to the prompt seen so far is
    lossless. The pools are ``[n_layers, n_blocks, bs, H * Dh]`` and
    carried through the layer scan like decode's
    (``_scan_layers_over_pool``): a layer scatters its K * C rows and the
    paged-attention kernel attends each lane's C rows to the pages under
    the lane's last valid position.
    Returns (first tokens [K] int32 — sampled with each request's
    settings at step 0, meaningful only on a lane's FINAL chunk — and
    the pools).
    """
    kk, c = chunks.shape
    n_ctx = btabs.shape[1]
    rows = jnp.arange(c, dtype=jnp.int32)
    positions = starts[:, None] + rows[None, :]                # [K, C]
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    x = (params["embed"]["tok"][chunks]
         + params["embed"]["pos"][safe_pos]).reshape(kk * c, cfg.d_model)
    valid = rows[None, :] < n_valids[:, None]                  # [K, C]
    blk = jnp.minimum(safe_pos // block_size, n_ctx - 1)
    dest = jnp.where(valid, jnp.take_along_axis(btabs, blk, axis=1),
                     0).reshape(kk * c)           # pad rows -> scratch
    off = (safe_pos % block_size).reshape(kk * c)
    lengths = jnp.where(valid, positions + 1, 1).reshape(kk * c)

    x, k_pool, v_pool = _scan_layers_over_pool(
        params, x, k_pool, v_pool, btabs, dest, off, c, lengths, cfg, mesh)
    last = jnp.take_along_axis(
        x.reshape(kk, c, cfg.d_model),
        (n_valids - 1).astype(jnp.int32)[:, None, None], axis=1,
    )[:, 0]                                                    # [K, d]
    logits = _head(params, last, cfg)                          # [K, vocab]
    firsts = lax.cond(
        jnp.any(temps > 0),
        lambda: _sample_slots(logits, seeds, jnp.zeros_like(seeds),
                              temps, topks),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32),
    )
    return firsts, k_pool, v_pool


class PagedModel:
    """What the scheduler knows of a model family, and all it knows.

    Admission, reservation, block tables, chunked prefill over context
    buckets, decode with its fused widths and delivery are the same for
    every family; the engine holds one of these and calls it. A family's
    steps take ``(params, *pools, ...)`` and give back ``(tokens, *pools,
    *extras)`` (fused: ``(token block, tokens, pos, steps, *pools,
    *extras)``), every pool donated: the engine carries the pools from one
    dispatch to the next and hands the extras, if there are any and
    stepscope is on, to its delivery thread with the dispatch's record.
    """

    cfg = None      # has ``max_len``: the positions served (the table's width)
    # Whether the family's attention reads the pages under a lane's length
    # (a paged kernel) or gathers its table: what a dispatch's ``kv_bytes``
    # are reckoned from. A gathering family's prefill chunk takes the whole
    # width of the table it is given, its decode step ``pages_gathered``.
    reads_pages_held = False
    # Whether a prompt's full pages may be handed to a later request with
    # the same prefix. A family whose layers keep state that those pages do
    # not hold (a window layer's ring) declines: ``_reserve`` then matches
    # and registers nothing.
    shares_prefix = True
    # Whether a prefill chunk's executable is specialised by its context (a
    # power-of-two bucket of table entries: what a table-wide gather wants),
    # or always given the whole table (a kernel whose grid is traced).
    prefill_by_context = True

    def pool_arrays(self, n_blocks: int, block_size: int) -> tuple:
        """The page pools, each ``[n_layers, n_blocks, block_size, ...]``."""
        raise NotImplementedError

    def block_bytes(self, block_size: int) -> int:
        """Bytes one block-table entry stands for, over every layer that
        keeps every position (a GLOBAL layer: all, but for a family with
        window layers)."""
        raise NotImplementedError

    # A family may have layers of a second kind: WINDOW layers, which keep a
    # bounded set of pages a slot whatever the request's length (a ring),
    # beside the block table's. The three below say what the scheduler has
    # to know of them; the defaults are a family without any.

    def ring_pages(self, block_size: int) -> int:
        """Entries of a slot's ring: a table row is ``[ring | block table]``,
        ring entry j of slot s being page ``1 + s * ring + j`` of the window
        layers' region (0 its scratch page)."""
        return 0

    def kind_bytes(self, block_size: int) -> tuple:
        """Bytes one page stands for over the layers of each kind:
        ``(global, window)``."""
        return self.block_bytes(block_size), 0

    def pages_read(self, length: int, rows: int, block_size: int) -> tuple:
        """Pages a layer of each kind reads for ``rows`` query rows of one
        table that end at context ``length``: ``(global, window)``."""
        return -(-length // block_size), 0

    def pages_gathered(self, longest: int, table_pages: int,
                       block_size: int) -> int:
        """Table entries a decode micro-step gathers for EACH slot of a bank
        whose longest live context is ``longest`` positions, in a family
        that gathers its table: the whole width, unless the family's step
        takes a narrower one (the latent family's does)."""
        return table_pages

    def attends_straight(self, rows: int) -> Optional[bool]:
        """Whether the family's paged-attention kernel takes its
        straight-line body for tables of ``rows`` query positions
        (``ops.paged_attention.straight_line`` of the family's head
        counts); None: its attention is no such kernel."""
        return None

    def shard(self, mesh, params):
        """``(params laid out on the mesh, the pools' sharding)``."""
        raise NotImplementedError

    def decode_step(self, block_size: int):
        """The function to jit as the whole-bank decode step; its name is
        the executable's on the device trace."""
        raise NotImplementedError

    def decode_fused(self, block_size: int, n_steps: int):
        raise NotImplementedError

    def prefill_chunk(self, block_size: int):
        raise NotImplementedError

    def routing(self, extras) -> Optional[dict]:
        """Counters for the dispatch record, from a step's extras (called
        on the delivery thread: it may read them back)."""
        return None


class GptPaged(PagedModel):
    """The GPT family: two pools of ``H * Dh`` (keys, values), learned
    positions, no extras. Its three wrappers look the step functions up in
    this module when traced, so the HLO modules and the profile's `XLA
    Modules` line read jit_decode_step / jit_decode_fused_<n> /
    jit_prefill_chunk."""

    reads_pages_held = True     # ops/paged_attention.py

    def __init__(self, cfg: GptConfig):
        self.cfg = cfg
        self._mesh = None     # set by ``shard``: the steps' kernel shards on it

    def pool_arrays(self, n_blocks: int, block_size: int):
        return _block_pool_arrays(self.cfg, n_blocks, block_size)

    def block_bytes(self, block_size: int) -> int:
        cfg = self.cfg
        try:
            itemsize = np.dtype(cfg.dtype).itemsize
        except TypeError:
            itemsize = 2  # bf16-family default
        return (cfg.n_layers * 2 * block_size * cfg.n_heads * cfg.head_dim
                * itemsize)

    def attends_straight(self, rows: int) -> bool:
        cfg, mesh = self.cfg, self._mesh
        # Heads are whole a shard: a shard's kernel sees its own.
        heads = cfg.n_heads // (1 if mesh is None
                                else int(dict(mesh.shape).get("tp", 1)))
        return straight_line(rows, heads, heads)

    def shard(self, mesh, params):
        from tritonclient_tpu.models.gpt import PARTITION_RULES
        from tritonclient_tpu.parallel.sharding import (
            named_sharding,
            shard_tree,
        )

        # Pool layout [n_layers, n_blocks, bs, H * Dh]: the flat axis on
        # tp, which keeps heads whole per shard (n_heads % tp == 0).
        # named_sharding drops absent/size-1 axes, so a tp-less mesh
        # degrades to replication like shard_tree does for params.
        self._mesh = mesh
        return (shard_tree(mesh, params, PARTITION_RULES),
                named_sharding(mesh, None, None, None, "tp"))

    def decode_step(self, block_size: int):
        cfg, mesh = self.cfg, self._mesh

        def decode_step(params, k_pool, v_pool, btabs, tokens, pos, seeds,
                        steps, temps, topks):
            return _decode_step_paged(
                params, k_pool, v_pool, btabs, tokens, pos, seeds, steps,
                temps, topks, cfg=cfg, block_size=block_size, mesh=mesh)

        return decode_step

    def decode_fused(self, block_size: int, n_steps: int):
        cfg, mesh = self.cfg, self._mesh

        def decode_fused(params, k_pool, v_pool, btabs, tokens, pos,
                         seeds, steps, temps, topks):
            return _decode_multi_step_paged(
                params, k_pool, v_pool, btabs, tokens, pos, seeds,
                steps, temps, topks, cfg=cfg, block_size=block_size,
                n_steps=n_steps, mesh=mesh)

        # One name per width: jit_decode_fused_<n> on the device trace.
        decode_fused.__name__ = f"decode_fused_{n_steps}"
        decode_fused.__qualname__ = decode_fused.__name__
        return decode_fused

    def prefill_chunk(self, block_size: int):
        cfg, mesh = self.cfg, self._mesh

        def prefill_chunk(params, k_pool, v_pool, chunks, btabs, starts,
                          n_valids, seeds, temps, topks):
            return _prefill_chunk_paged(
                params, k_pool, v_pool, chunks, btabs, starts, n_valids,
                seeds, temps, topks, cfg=cfg, block_size=block_size,
                mesh=mesh)

        return prefill_chunk


def wire_tensors():
    """The engine models' wire contract: ``(inputs, outputs)``."""
    return ([
        TensorSpec("INPUT_IDS", "INT32", [-1, -1]),
        TensorSpec("MAX_TOKENS", "INT32", [1], optional=True),
        TensorSpec("TEMPERATURE", "FP32", [1], optional=True),
        TensorSpec("TOP_K", "INT32", [1], optional=True),
        TensorSpec("SEED", "INT64", [1], optional=True),
    ], [TensorSpec("OUTPUT_IDS", "INT32", [-1])])


class _Request:
    __slots__ = ("prompt", "max_new", "out", "remaining", "temperature",
                 "top_k", "seed", "cancelled", "cancel_event",
                 "steps_completed", "mem_owner", "kv_pages_held", "span")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 cancel_event=None, span=None):
        self.prompt = prompt
        self.max_new = max_new
        self.remaining = max_new
        # stepscope's timeline of this request (None while it is off: the
        # check is made once, at submit). Each stamp is written by the one
        # thread that owns that moment; ``end`` hands it to the ring.
        self.span = span
        # Tokens delivered so far (delivery-thread-owned, like remaining).
        # Mirrored onto the cancel_event so shed/cancel finalization in the
        # core can stamp WHERE in the decode loop the request died — a
        # cancelled request's flight record otherwise shows only wall time.
        self.steps_completed = 0
        # Memscope attribution token (assigned at submit) and the page
        # reservation granted at admission. Mirrored onto the
        # cancel_event like steps_completed, so shed/cancel finalization
        # can stamp died-holding-N-pages onto the flight record.
        self.mem_owner = ""
        self.kv_pages_held = 0
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.cancelled = False  # set by the consumer; engine frees the slot
        # Transport-armed cancellation (threading.Event or None): the
        # engine loop polls it between decode steps — a client that
        # disconnects frees its slot within one step even if the response
        # generator is parked in a queue.get.
        self.cancel_event = cancel_event
        self.out: "queue.Queue" = queue.Queue()

    @property
    def abandoned(self) -> bool:
        return self.cancelled or (
            self.cancel_event is not None and self.cancel_event.is_set()
        )

    def end(self, terminator, outcome: str):
        """Put the request's last item (None or the error). The timeline
        reaches stepscope's ring first, so a consumer that has seen the
        terminator finds the record there."""
        _stepscope.request_end(self.span, outcome)
        self.out.put(terminator)


class _PrefillState:
    """A slot whose prompt is still streaming into its pages.

    ``blocks`` is the FULL reservation (prefix-cache shares first, then
    fresh pages for the rest of the prompt and the whole decode budget);
    ``next`` is the next prompt index to feed (starts past the shared
    pages); ``hashes`` are the cumulative block hashes of the matchable
    full prompt blocks — entries past ``n_hit`` register in the prefix
    cache when the prefill completes.
    """

    __slots__ = ("req", "prompt_len", "blocks", "n_hit", "hashes",
                 "next")

    def __init__(self, req: "_Request", prompt_len: int,
                 blocks: List[int], n_hit: int, hashes: List[int]):
        self.req = req
        self.prompt_len = prompt_len
        self.blocks = blocks
        self.n_hit = n_hit
        self.hashes = hashes
        self.next = 0


class _Distributor:
    """Token delivery decoupled from the engine loop (prefill priority).

    The engine loop used to block on the previous dispatch's readback
    (``np.asarray``) every iteration, so a request arriving mid-flight
    waited a full readback before its prefill
    could even DISPATCH — the TTFT-under-load term VERDICT r4 #4 calls
    out. Deliveries now drain FIFO on this thread; the engine loop only
    dispatches (prefills + steps) and never touches a host copy, so
    admission cadence is decoupled from readback latency.

    A bounded window (``max_inflight`` tickets) stops compute running
    unboundedly ahead of delivery. Slot-freeing on completion is routed
    back to the engine loop through ``free_q`` (one item a dispatch: the
    ``(slot, request)`` pairs it finished, so the loop frees a burst in one
    pass) — slot state stays single-threaded.
    """

    __slots__ = ("q", "prio_q", "free_q", "_sem", "_thread", "_engine")

    max_inflight = 3    # tickets: the one value every run on record used

    def __init__(self, engine: "GenerationEngine"):
        self.q: "queue.Queue" = queue.Queue()
        # First-token (prefill) deliveries jump the line: a prefill item
        # is always its request's FIRST delivery, so overtaking OTHER
        # requests' step deliveries cannot reorder anyone's stream — and
        # it stops TTFT from queuing behind up to max_inflight step
        # readbacks (~a readback RTT each on remote links).
        self.prio_q: "queue.Queue" = queue.Queue()
        self.free_q: "queue.Queue" = queue.Queue()
        self._sem = threading.Semaphore(self.max_inflight)
        self._thread: Optional[threading.Thread] = None
        self._engine = engine

    def dispatch_ticket(self):
        """Block until the in-flight window has room (engine loop side)."""
        self._sem.acquire()

    def try_ticket(self, timeout: float) -> bool:
        return self._sem.acquire(timeout=timeout)

    def release_ticket(self):
        """Return an acquired-but-unused ticket (no dispatch happened)."""
        self._sem.release()

    def submit(self, nxt_dev, pairs, first_token: bool = False,
               scope=None):
        """``first_token`` (prefill) items ride the priority lane AND
        are exempt from the in-flight ticket window: admissions are
        already bounded by the slot count, and making a new request's
        prefill wait for a step-readback ticket (~a readback RTT) is
        exactly the TTFT-under-load term. Step items take/release
        tickets as usual. ``scope`` is the dispatch's stepscope record
        (None when off): the item carries a delivery record of its own,
        which the delivery thread stamps and closes."""
        self._start()
        delivery = _stepscope.delivery_begin(scope)
        if first_token:
            self.prio_q.put(("deliver", nxt_dev, pairs, delivery))
            self.q.put(("prio",))  # wake marker preserving queue blocking
        else:
            self.q.put(("deliver", nxt_dev, pairs, delivery))

    def submit_routing(self, scope, extras):
        """What a step returned beside its tokens and pools (a routed
        family's histogram), for the dispatch's record: queued behind the
        dispatch's own item and read back on this thread, so the engine
        loop waits for nothing. Only when stepscope is on and the family
        returns something; call it after ``step_end``."""
        if scope is not None and extras:
            self._start()
            self.q.put(("routing", scope, extras))

    def submit_cancel(self, req):
        """Terminate a cancelled request IN DELIVERY ORDER: the None
        terminator lands after every token already in the pipe, and
        ``req.remaining``/``req.out`` stay delivery-thread-owned (no
        unsynchronized engine-loop mutation racing ``_deliver``)."""
        self._start()
        self.q.put(("cancel", req))

    def _start(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="gpt-engine-deliver"
            )
            self._thread.start()

    def drain_and_stop(self, timeout: float = 10.0):
        t = self._thread
        if t is not None and t.is_alive():
            self.q.put(None)
            t.join(timeout=timeout)
        self._thread = None

    # tpulint: hot-path
    def _run(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        while True:
            # Priority lane first: pending first-token deliveries beat
            # everything already queued. Prefill items never hold a
            # dispatch ticket (see submit), so only q-sourced "deliver"
            # items release the semaphore.
            ticketed = False
            try:
                item = self.prio_q.get_nowait()
            except queue.Empty:
                item = self.q.get()
                if item is None:
                    return
                if item[0] == "prio":
                    # Wake marker: its payload lives in prio_q (it may
                    # already have been drained by an earlier pass).
                    try:
                        item = self.prio_q.get_nowait()
                    except queue.Empty:
                        continue
                else:
                    ticketed = item[0] == "deliver"
            if item[0] == "cancel":
                # Control item: no dispatch ticket to release.
                req = item[1]
                if req.remaining > 0:
                    req.remaining = 0
                    req.end(None, _stepscope.OUTCOME_CANCELLED)
                continue
            if item[0] == "routing":
                # Control item too. A readback that fails here fails the
                # dispatch's own delivery as well, which reports it.
                try:
                    _stepscope.step_routing(
                        item[1], self._engine._model.routing(item[2]))
                except Exception:  # noqa: BLE001
                    pass
                continue
            delivery = item[3]
            if delivery is not None:
                delivery["taken_ns"] = time.monotonic_ns()
            try:
                self._deliver(item[1], item[2], delivery)
            except BaseException as e:  # noqa: BLE001 — surface, don't die silently
                # A failed readback poisons the engine the same way a
                # failed dispatch does: consumers of this dispatch get the
                # error, the engine loop sees _broken at its next top.
                for _, _, req in item[2]:
                    req.end(e, _stepscope.OUTCOME_ERROR)
                with self._engine._cv:
                    if self._engine._broken is None:
                        self._engine._broken = e
                    self._engine._cv.notify_all()
            finally:
                _stepscope.delivery_end(delivery)
                if ticketed:
                    self._sem.release()
                    _stepscope.inflight_update(
                        self._engine._scope_name, -1
                    )

    def _deliver(self, nxt_dev, pairs, delivery=None):
        """Deliver one dispatch's tokens (one readback serves them all).

        `pairs` (index-in-array, slot, request) binds each delivery to the
        request that occupied the slot AT DISPATCH time: with the pipeline
        a slot can be freed and re-admitted before its last computed token
        is delivered, and a completed request's surplus step (computed
        while its final token was still in flight) must be dropped, not
        delivered to the slot's new occupant.

        A fused dispatch hands over ``[n_steps, S]`` (one row per
        micro-step); rows deliver in step order, so per-request token
        order is exactly the lockstep pipeline's, and a request whose
        budget runs out mid-block simply drops the surplus rows.

        stepscope (``delivery`` and each request's ``span``, None when
        off): ``ready_ns`` is when THIS thread saw the readback return, in
        the order it serves its two queues — not when the device finished.
        """
        nxt_np = np.asarray(nxt_dev)
        ready_ns = 0
        if delivery is not None:
            ready_ns = _stepscope.delivery_ready(delivery)
        rows = nxt_np if nxt_np.ndim == 2 else nxt_np[None]
        finished = []
        for t in range(rows.shape[0]):
            row = rows[t]
            for idx, slot, req in pairs:
                if req.remaining <= 0:
                    continue  # surplus step of an already-finished request
                span = req.span
                if span is not None:
                    now = time.monotonic_ns()
                    if span.first_ready_ns is None:
                        # A request's first delivery is its first-token
                        # item (the priority lane).
                        span.first_ready_ns = ready_ns or now
                    span.out_ns.append(now)
                req.out.put(row[idx : idx + 1].copy())
                req.remaining -= 1
                req.steps_completed += 1
                if req.cancel_event is not None:
                    # Event objects double as the steps_completed side
                    # channel back to the core's cancel finalization (the
                    # engine never sees the request's TraceContext).
                    try:
                        req.cancel_event.steps_completed = (
                            req.steps_completed
                        )
                    except AttributeError:
                        pass
                if req.remaining == 0:
                    req.end(None, _stepscope.OUTCOME_FINISHED)
                    finished.append((slot, req))
        if finished:
            self.free_q.put(finished)
            with self._engine._cv:
                self._engine._cv.notify_all()
        if delivery is not None:
            _stepscope.delivery_delivered(delivery)


# The columns of ``_update_slots``' one host-built argument, ``[S, 7 +
# max_blocks]`` int32: a slot's lane in the prefill's result, whether it is
# joined or freed, its position, seed and top-k, its temperature's float32
# bits, then its block-table row.
_W_LANE, _W_JOINED, _W_FREED, _W_POS, _W_SEED, _W_TOPK, _W_TEMP, _W_ROW = (
    range(8))


def _update_slots(btabs, tokens, pos, seeds, steps, temps, topks, firsts,
                  writes):
    """Every write a join, a free or a cancel makes to the per-slot device
    state, as ONE program of fixed shapes: the engine loop calls it at most
    once for the frees and once for the joins of a pass.

    The state vectors are ``[S]`` (``btabs`` ``[S, max_blocks]``);
    ``writes`` is one NumPy array over all the slots (columns ``_W_*``). It
    is ONE array because on the loop's thread every transfer and every
    dispatch gives the interpreter lock up and has to get it back from the
    server's stream handlers: under eight contending threads a call with
    eight NumPy arguments took 31 ms on the chip's host, with one 15, and
    twenty eager scatters and transfers 544 (PERF.md §6, PR 30). A joined
    slot takes its block-table row, position, seed, temperature and top-k
    from ``writes``, ``steps`` 1, and its token from the prefill's result HERE
    (``firsts[lane]``: no slice, no concatenate and no second host copy
    on the loop's thread). A freed slot takes the all-scratch row, position
    0 and temperature 0.0 (an all-greedy bank goes back down the step's
    cheap argmax branch); every other slot keeps what it has. Nothing
    depends on how many slots a call carries: ``firsts``' lane bucket is
    the only shape that varies. Nothing is donated: in the unfused branch
    ``tokens`` IS the array the delivery thread reads back.
    """
    joined = writes[:, _W_JOINED] > 0
    # A freed slot's other columns are zeros: the scratch page, position
    # 0, and the bits of 0.0.
    written = joined | (writes[:, _W_FREED] > 0)
    temps_new = lax.bitcast_convert_type(writes[:, _W_TEMP], jnp.float32)
    return (
        jnp.where(written[:, None], writes[:, _W_ROW:], btabs),
        jnp.where(joined, firsts[writes[:, _W_LANE]], tokens),
        jnp.where(written, writes[:, _W_POS], pos),
        jnp.where(joined, writes[:, _W_SEED], seeds),
        jnp.where(joined, 1, steps),
        jnp.where(written, temps_new, temps),
        jnp.where(joined, writes[:, _W_TOPK], topks),
    )


class GenerationEngine:
    """The continuous-batching scheduler around the paged block pool."""

    def __init__(self, cfg, params: Dict, max_slots: int = 8,
                 mesh=None, scope_name: str = "gpt_engine",
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 prefill_chunk: int = 32):
        """``cfg``: a ``GptConfig`` (the GPT family) or any ``PagedModel``;
        from here on the engine asks the family for its pools, its steps
        and its bytes a page, and for nothing else.

        ``mesh``: run the engine tensor-parallel — params laid out by
        the Megatron rules (models/gpt.PARTITION_RULES) and the paged
        KV pool sharded on its flat heads axis over 'tp', so continuous
        batching scales past one chip's HBM/FLOPs. Greedy decoding stays
        token-identical to the single-device path (GSPMD inserts the
        all-reduces through prefill chunks, the batched decode step, and
        the logits head; tested).

        ``block_size`` must divide ``cfg.max_len`` — the gathered view
        then has exactly the contiguous bank's [S, max_len] geometry, so
        paging is a memory-layout change, never a numerics change.
        ``n_blocks`` defaults to full per-slot capacity plus the scratch
        page (1 + max_slots * max_len/block_size): identical admission
        behavior to the old slot bank unless the caller sizes the pool
        smaller. ``prefill_chunk`` is the single compiled prefill shape.
        """
        model = cfg if isinstance(cfg, PagedModel) else GptPaged(cfg)
        self._model = model
        self.cfg = cfg = model.cfg
        self.mesh = mesh
        if cfg.max_len % block_size:
            raise ValueError(
                f"block_size {block_size} must divide max_len "
                f"{cfg.max_len} (the gathered view must reconstruct the "
                "dense cache geometry exactly)"
            )
        self.block_size = block_size
        self._max_blocks = cfg.max_len // block_size   # per-slot table width
        # Bytes one page stands for over the layers of each kind (global,
        # window): the accounting units of admission, memscope and
        # stepscope's kv_bytes. A block-table entry is a global page.
        self._kind_bytes = tuple(model.kind_bytes(block_size))
        self._block_kv_bytes = self._kind_bytes[0]
        # Entries of a slot's ring (0: the family has no window layers); a
        # table row is [ring | block table].
        self._ring = model.ring_pages(block_size)
        self._table_width = self._ring + self._max_blocks
        if n_blocks is None:
            n_blocks = 1 + max_slots * self._max_blocks
        self.prefill_chunk = max(1, min(int(prefill_chunk), cfg.max_len))
        if mesh is not None:
            from tritonclient_tpu.parallel.sharding import named_sharding

            params, self._cache_sharding = model.shard(mesh, params)
            self._vec_sharding = named_sharding(mesh)
        else:
            self._cache_sharding = None
            self._vec_sharding = None
        self.params = params
        # Parameter bytes on the ledger: per-device resident bytes from
        # the ACTUAL jax.Array shardings (a tp mesh splits a leaf across
        # devices; replication charges every device its full size).
        _memscope.register_params(scope_name, params)
        self.max_slots = max_slots
        # The family's page pools, carried from one dispatch to the next
        # (every step donates them and gives them back).
        if self._cache_sharding is not None:
            # Allocate the pools directly sharded: staging the full
            # unsharded [L, n_blocks, bs, H * Dh] zeros on one device
            # first would OOM exactly the configs the mesh exists for.
            self._pools = tuple(jax.jit(
                lambda: model.pool_arrays(n_blocks, block_size),
                out_shardings=self._cache_sharding,
            )())
        else:
            self._pools = tuple(model.pool_arrays(n_blocks, block_size))
        # Host-side allocation state. The first alloc deterministically
        # returns page 0 — pinned forever as the SCRATCH page that idle
        # and still-prefilling slots write into.
        self._pool = _kvcache.BlockPool(n_blocks, block_size)
        self._prefix = _kvcache.PrefixCache(self._pool)
        # Ledger identity BEFORE the scratch alloc: the pinned scratch
        # page is resident from birth and belongs on the ledger.
        _kvcache.attach_memscope(self._pool, self._prefix, scope_name,
                                 self._block_kv_bytes)
        self._scratch = self._pool.try_alloc()
        assert self._scratch == 0
        self._slot_blocks: List[List[int]] = [[] for _ in range(max_slots)]
        self._prefilling: Dict[int, _PrefillState] = {}
        self._pending: Optional[_Request] = None  # head-of-line, blocked on pages
        self._btabs = jnp.zeros((max_slots, self._table_width), jnp.int32)
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._pos = jnp.zeros((max_slots,), jnp.int32)
        # Per-slot sampling state (request settings + the (seed, step)
        # key-schedule counters), all device-resident.
        self._seeds = jnp.zeros((max_slots,), jnp.int32)
        self._steps = jnp.zeros((max_slots,), jnp.int32)
        self._temps = jnp.zeros((max_slots,), jnp.float32)
        self._topks = jnp.zeros((max_slots,), jnp.int32)
        if self._vec_sharding is not None:
            # Slot-state vectors replicate over the mesh so every jit sees
            # one device set (params/caches are mesh-committed).
            self._btabs, self._tokens, self._pos, self._seeds, \
                self._steps, self._temps, self._topks = jax.device_put(
                    (self._btabs, self._tokens, self._pos, self._seeds,
                     self._steps, self._temps, self._topks),
                    self._vec_sharding,
                )
        # Slot-state scratch buffers on the ledger (the KV pool arrays
        # themselves are the kv pool's declared capacity).
        _memscope.set_static(
            scope_name, _memscope.MEM_POOL_SCRATCH, "slot_state",
            int(sum(int(a.nbytes) for a in (
                self._btabs, self._tokens, self._pos, self._seeds,
                self._steps, self._temps, self._topks))),
            {"buffers": "btabs/tokens/pos/seeds/steps/temps/topks"},
        )
        if self._ring:
            # The window layers' rings belong to the slots, not to the
            # requests: a static population beside the block pool's pages.
            _memscope.set_static(
                scope_name, _memscope.MEM_POOL_SCRATCH, "kv_window_rings",
                (1 + max_slots * self._ring) * self._kind_bytes[1],
                {"pages_a_slot": self._ring, "slots": max_slots},
            )
        self._slot_req: List[Optional[_Request]] = [None] * max_slots
        self._req_seq = 0  # memscope owner tokens (guarded by _cv)
        self._admit: "queue.Queue" = queue.Queue()
        # Named for the tpusan lock-order witness (plain Condition when
        # the sanitizer is inactive).
        self._cv = sanitize.named_condition("GenerationEngine._cv")
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._broken: Optional[BaseException] = None
        self._dist = _Distributor(self)
        # stepscope identity: records carry the serving model's name, and
        # tp engines charge the per-step all-reduce count the gpt
        # PARTITION_RULES provably force (GSPMD inserts them implicitly —
        # there is no python call site to count at).
        self._scope_name = scope_name
        tp = int(dict(mesh.shape).get("tp", 1)) if mesh is not None else 1
        self._expected_collectives = _stepscope.expected_tp_collectives(
            cfg.n_layers, tp
        )
        self._prefill_seq = 0
        # The engine's executables are jitted under the names the family
        # gives its step functions (the GPT family: jit_decode_step /
        # jit_decode_fused_<n> / jit_prefill_chunk on the profile's `XLA
        # Modules` line). Every pool is donated.
        self._donate = tuple(range(1, 1 + len(self._pools)))
        self._step = jax.jit(model.decode_step(block_size),
                             donate_argnums=self._donate)
        # Unfused-branch slot clocks advance through a donating jit so
        # the dead pos/steps buffers are reused in place on TPU.
        self._advance = jax.jit(_advance_slot_clocks, donate_argnums=(0, 1))
        # Joins, frees and cancels write the slot state through one jitted
        # update (replicated over a mesh, as the vectors are).
        self._update_slots = jax.jit(_update_slots,
                                     out_shardings=self._vec_sharding)
        # Fused pipelined dispatch: TPU_ENGINE_FUSE_STEPS=k scans k decode
        # micro-steps into one dispatch + one readback when the bank is
        # saturated (no prefills, empty admission queue, every active
        # request still owes ≥ k tokens). Compiled lazily per bucketed k.
        self._fuse_steps = max(
            int(os.environ.get("TPU_ENGINE_FUSE_STEPS", "4")), 1
        )
        self._multi_step: Dict[int, object] = {}
        self._dispatched = [0] * max_slots  # decode tokens dispatched/slot
        self._prefill_chunk_fn = jax.jit(
            model.prefill_chunk(block_size), donate_argnums=self._donate)
        # /metrics registry: weakly bound so a dropped engine vanishes
        # from the exposition instead of being pinned by it.
        import weakref

        ref = weakref.ref(self)

        def _kv_snapshot():
            e = ref()
            if e is None:
                raise RuntimeError("engine gone")
            return {
                "used": e._pool.used_count,
                "total": e._pool.n_blocks,
                "events": e._prefix.snapshot_events(),
            }

        _kvcache.register(scope_name, self, _kv_snapshot)
        # The daemon loop must not be frozen mid-XLA-call at interpreter
        # exit (the runtime aborts on an unraisable C++ exception); stop
        # and join it from atexit. Weakref so the hook never extends the
        # engine's lifetime.
        import atexit

        atexit.register(lambda: (lambda e: e and e.shutdown())(ref()))

    def _note_attention(self, scope, lanes, gathered: int, slots,
                        rows_per_table: int = 1):
        """What a dispatch's attention reads, on its record. ``lanes``:
        ``(context length, query rows)`` of each real lane (and micro-step);
        ``rows_per_table``: the query positions a table of the executable
        (the chunk's length; 1 in decode), from which ``attn_straight``:
        which body of the paged kernel the executable holds.
        ``ctx_pages``: the table entries under the lanes' lengths, what a
        global layer's kernel visits. ``kv_bytes``: by kind, the pages the
        kernel visits where the family's kernel reads the pages held (hit
        pages too); where it gathers the table, the entries gathered
        (``gathered``, ``pages_gathered`` on the record: every lane's and
        micro-step's width taken of its table). And on a family with
        window layers the split by kind: pages read, and bytes held by the
        requests of ``slots``."""
        bs, most = self.block_size, self._max_blocks
        each = [self._model.pages_read(length, rows, bs)
                for length, rows in lanes]
        read = (sum(min(g, most) for g, _ in each),
                sum(min(w, most) for _, w in each))
        scope.ctx_pages = read[0]
        if self._model.reads_pages_held:
            scope.kv_bytes = sum(
                n * b for n, b in zip(read, self._kind_bytes))
        else:
            scope.pages_gathered = gathered
            scope.kv_bytes = self._block_kv_bytes * gathered
        scope.attn_straight = self._model.attends_straight(rows_per_table)
        if not self._ring:
            return
        scope.ctx_pages_global, scope.ctx_pages_window = read
        held = [self.held_bytes(self._slot_req[s].kv_pages_held)
                for s in slots if self._slot_req[s] is not None]
        scope.kv_held_global = sum(g for g, _ in held)
        scope.kv_held_window = sum(w for _, w in held)

    def held_bytes(self, n_pages: int) -> tuple:
        """Bytes a request with ``n_pages`` block-table entries holds, by
        kind ``(global, window)``: a window layer holds its ring's pages and
        no more, whatever the request's length."""
        return (n_pages * self._kind_bytes[0],
                min(n_pages, self._ring) * self._kind_bytes[1])


    def _keep_pools(self, result, lead: int):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        """Take the pools back from a step's result ``(lead arrays, *pools,
        *extras)``; returns ``(the lead arrays, the extras)``."""
        n = lead + len(self._pools)
        self._pools = tuple(result[lead:n])
        return result[:lead], result[n:]

    # The GPT family's two pools under the names its benchmark adapter,
    # chip_smoke.py and the tests take and put them by.
    @property
    def _k(self):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        return self._pools[0]

    @_k.setter
    def _k(self, pool):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        self._pools = (pool,) + self._pools[1:]

    @property
    def _v(self):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        return self._pools[1]

    @_v.setter
    def _v(self, pool):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        self._pools = self._pools[:1] + (pool,) + self._pools[2:]

    def release_pools(self):  # tpulint: disable=TPU002,TPU009 - the pools change hands on the engine-loop thread only (warm-ups and take-down run on an idle or stopped engine)
        """Free the page pools' device memory (after ``shutdown``: whoever
        takes the engine down to give the device to something else)."""
        for pool in self._pools:
            if pool is not None and not pool.is_deleted():
                pool.delete()
        self._pools = (None,) * len(self._pools)

    def shutdown(self, timeout: float = 10.0):
        """Stop the engine loop (in-flight step finishes; queued and
        active requests receive their terminator)."""
        with self._cv:
            # Stop flag and thread handle read/written under the cv: the
            # engine loop must observe the flag no later than the wakeup.
            self._stopping = True
            t = self._thread
            self._cv.notify_all()
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
        self._dist.drain_and_stop(timeout=timeout)
        self._process_frees()
        self._drain_terminated()
        _kvcache.unregister(self._scope_name, self)
        # Ledger closure: the pool's device arrays leave the serving set
        # — every resident byte (scratch + parked cache pages) frees and
        # the headroom row retires. Idempotent (live already 0 on a
        # second shutdown).
        _memscope.pool_close(self._scope_name, _memscope.MEM_POOL_KV)
        _memscope.drop_scope(self._scope_name)

    def _drain_terminated(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Terminate every queued/active request (no thread will serve
        them): admission-queue waiters too, not just slot occupants."""
        cancelled = _stepscope.OUTCOME_CANCELLED
        if self._pending is not None:
            self._pending.end(None, cancelled)
            self._pending = None
        while True:
            try:
                self._admit.get_nowait().end(None, cancelled)
            except queue.Empty:
                break
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                req.end(None, cancelled)
                self._prefilling.pop(slot, None)
                self._free_slot_blocks(slot)
                self._slot_req[slot] = None

    # -- client side ---------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int,
               temperature: float = 0.0, top_k: int = 0,
               seed: int = 0, cancel_event=None,
               timestamps=None) -> "_Request":
        """Queue a generation; returns the _Request whose ``.out`` queue
        yields np [1] per token, then None. Setting ``.cancelled`` (or
        arming ``cancel_event``) frees the slot — and returns its KV
        pages to the pool — at the engine's next loop top, i.e. within
        one decode step. Greedy by default; temperature/top_k/seed follow
        the shared sampling key schedule (gpt.sampling_key).
        ``timestamps`` is the request's TraceContext timeline where the
        core handed one down: stepscope copies its receipt stamps onto
        the request's record (nothing is read while stepscope is off)."""
        if prompt.shape[1] >= self.cfg.max_len:
            raise ValueError(
                f"prompt length {prompt.shape[1]} must be < max_len "
                f"{self.cfg.max_len}"
            )
        max_new = max(1, min(max_new,
                             self.cfg.max_len - prompt.shape[1]))
        # 31-bit canonical form (matches sampling_key) so the int32 slot
        # vectors hold any int64 wire seed without overflow.
        prompt = prompt.astype(np.int32)
        req = _Request(prompt, max_new, temperature,
                       top_k, int(seed) & 0x7FFFFFFF,
                       cancel_event=cancel_event,
                       span=_stepscope.request_begin(
                           self._scope_name, prompt, max_new, timestamps))
        with self._cv:
            if self._stopping:
                raise RuntimeError("generation engine is shut down")
            if self._broken is not None:
                raise RuntimeError(
                    f"generation engine failed: {self._broken}"
                )
            self._req_seq += 1
            req.mem_owner = f"{self._scope_name}.r{self._req_seq}"
            self._admit.put(req)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="gpt-engine"
                )
                self._thread.start()
            self._cv.notify_all()
        return req

    # -- block accounting ----------------------------------------------------

    def _free_slot_blocks(self, slot: int):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Return a slot's pages (block-granular, immediately reusable).

        Registered pages park on the prefix cache's evictable LRU (their
        KV stays warm); unregistered ones go straight to the free list.
        The host half of a free, and all of it on the shutdown and broken
        paths, where no further dispatch will happen and the device may be
        unusable. While the engine serves, the caller hands the slot to
        ``_write_slot_state`` in the same pass: that re-points the slot's
        block-table row at the scratch page, so in-flight/surplus decode
        writes for this slot can no longer land in pages a NEW request may
        get — the paged equivalent of the contiguous bank's harmless
        garbage writes.
        """
        req = self._slot_req[slot]
        owner = req.mem_owner if req is not None else ""
        if owner:
            _memscope.push_owner(owner)
        try:
            for bid in self._slot_blocks[slot]:
                self._prefix.release_block(bid)
        finally:
            if owner:
                _memscope.pop_owner()
        self._slot_blocks[slot] = []
        if owner:
            # Reconciliation point: the request's pages are back, so its
            # ledger bytes must be exactly zero — nonzero residue is a
            # leak (TPU012 finding under the sanitizer).
            _memscope.owner_finish(self._scope_name,
                                   _memscope.MEM_POOL_KV, owner)

    def _write_slot_state(self, firsts, joins=(), frees=()):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """One ``_update_slots`` dispatch: ``joins`` are ``(lane, slot,
        prefill state)`` of the prompts the chunk dispatch behind
        ``firsts`` finished, ``frees`` the slots whose requests ended. What
        they write is built here as one NumPy array over all the slots, so
        the executable is the same whatever the burst carries; stepscope
        gets one record a call (how many slots, and what the call cost
        this thread)."""
        began = _stepscope.clock()
        writes = np.zeros((self.max_slots, _W_ROW + self._table_width),
                          np.int32)
        writes[list(frees), _W_FREED] = 1
        for lane, slot, st in joins:
            req = st.req
            writes[slot, :_W_ROW] = (
                lane, 1, 0, st.prompt_len, req.seed, req.top_k,
                np.float32(req.temperature).view(np.int32))
            writes[slot, _W_ROW:] = self._table_row(
                slot, st.blocks, self._max_blocks)
        (self._btabs, self._tokens, self._pos, self._seeds, self._steps,
         self._temps, self._topks) = self._update_slots(
            self._btabs, self._tokens, self._pos, self._seeds, self._steps,
            self._temps, self._topks, firsts, writes)
        _stepscope.slot_update(self._scope_name, len(joins), len(frees),
                               began, _stepscope.clock())

    def _table_row(self, slot: int, blocks, n_ctx: int) -> np.ndarray:
        """A slot's table row as the family's steps take it: its ring's
        entries (none where the family has no window layers), then the
        first ``n_ctx`` entries of its block table."""
        ring = self._ring
        row = np.zeros((ring + n_ctx,), np.int32)
        row[:ring] = 1 + slot * ring + np.arange(ring)
        k_ctx = min(len(blocks), n_ctx)
        row[ring:ring + k_ctx] = blocks[:k_ctx]
        return row

    def _alloc_block(self) -> Optional[int]:
        """A free page, evicting the LRU zero-ref cached page if needed."""
        bid = self._pool.try_alloc()
        if bid is None:
            bid = self._prefix.evict_lru()
        return bid

    def _reserve(self, req: "_Request"):
        """Try to reserve the request's FULL page budget
        (ceil((prompt + max_new) / block_size)) — hit pages from the
        prefix cache, the rest fresh. All-at-admission reservation keeps
        decode allocation-free, hence deadlock-free; failure rolls back
        and the request waits at the head of the line. Returns a
        _PrefillState, None (pool exhausted — retry on free), or an
        exception (request can NEVER fit this pool)."""
        bs = self.block_size
        l = req.prompt.shape[1]
        n_total = min(-(-(l + req.max_new) // bs), self._max_blocks)
        if n_total > self._pool.n_blocks - 1:
            return RuntimeError(
                f"request needs {n_total} KV pages but the pool holds "
                f"{self._pool.n_blocks - 1} (block_size {bs}); size the "
                "pool for at least one full-length request"
            )
        # Matchable prefix: full prompt blocks only, and always leave at
        # least the last prompt token to compute (its logits produce the
        # first output token).
        prompt_row = req.prompt[0]
        hashes: List[int] = []
        h = 0
        # A family that declines sharing matches nothing and, with no
        # hashes, registers nothing when the prompt is in.
        shared = (l - 1) // bs if self._model.shares_prefix else 0
        for i in range(shared):
            h = _kvcache.block_hash(h, prompt_row[i * bs:(i + 1) * bs])
            hashes.append(h)
        blocks: List[int] = []
        n_hit = 0
        # Memscope attribution bracket: every page granted (fresh or
        # shared hit) inside it is charged to this request's owner
        # token; a rollback discharges symmetrically.
        owner = req.mem_owner
        if owner:
            _memscope.owner_begin(
                self._scope_name, _memscope.MEM_POOL_KV, owner,
                prompt_len=int(l), max_new=int(req.max_new),
                pages=int(n_total),
            )
            _memscope.push_owner(owner)
        try:
            for hk in hashes:
                bid = self._prefix.match(hk)
                if bid is None:
                    break
                blocks.append(bid)
                n_hit += 1
            ok = True
            for _ in range(n_total - n_hit):
                bid = self._alloc_block()
                if bid is None:
                    ok = False
                    break
                blocks.append(bid)
            if not ok:
                for bid in blocks:
                    self._prefix.release_block(bid)
        finally:
            if owner:
                _memscope.pop_owner()
        if not ok:
            if owner:
                _memscope.owner_discard(self._scope_name,
                                        _memscope.MEM_POOL_KV, owner)
            return None
        # Events count once per COMMITTED admission (never per blocked
        # retry): every matchable block is either a hit or a miss.
        if n_hit:
            self._prefix.count(PREFIX_EVENT_HIT, n_hit)
        if len(hashes) - n_hit:
            self._prefix.count(PREFIX_EVENT_MISS, len(hashes) - n_hit)
        req.kv_pages_held = n_total
        if req.cancel_event is not None:
            # Pages-held side channel to the core's shed/cancel
            # finalization, exactly like steps_completed in _deliver.
            try:
                req.cancel_event.kv_pages_held = n_total
                req.cancel_event.kv_bytes_held = sum(
                    self.held_bytes(n_total))
            except AttributeError:
                pass
        st = _PrefillState(req, l, blocks, n_hit, hashes)
        st.next = n_hit * bs
        return st

    # -- engine loop ---------------------------------------------------------

    def _multi_step_fn(self, n_steps: int):  # tpulint: disable=TPU009 - engine-loop-only jit cache (sole mutator)
        """The jitted fused decode for one bucketed micro-step count
        (compiled on first use; the bucket set is the powers of two up to
        TPU_ENGINE_FUSE_STEPS, so the shape family stays tiny)."""
        fn = self._multi_step.get(n_steps)
        if fn is None:
            fn = self._multi_step[n_steps] = jax.jit(
                self._model.decode_fused(self.block_size, n_steps),
                donate_argnums=self._donate)
        return fn

    def _choose_fuse(self, active: List[int]) -> int:  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Micro-steps for the next dispatch. Fusing trades scheduler
        granularity for dispatch amortization, so it only engages when
        nothing is waiting on the scheduler: no prefilling slot, an empty
        admission queue, no head-of-line request — and never past the
        smallest remaining token budget in the bank (bucketed to a power
        of two to bound the compile family). Cancels/deadlines are still
        polled between dispatches, so the cancel window is bounded by
        max_inflight × fuse micro-steps."""
        fuse = self._fuse_steps
        if fuse <= 1:
            return 1
        if (self._prefilling or self._pending is not None
                or not self._admit.empty()):
            return 1
        left = fuse
        for s in active:
            req = self._slot_req[s]
            if req is None:
                return 1
            left = min(left, req.max_new - self._dispatched[s])
        if left <= 1:
            return 1
        return 1 << (min(left, fuse).bit_length() - 1)

    def _release_cancelled(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """A consumer that went away (stream closed) marks its request
        cancelled; its slot AND its KV pages free at the next loop top
        instead of generating dead tokens until max_new. Termination
        itself is routed through the delivery queue (submit_cancel) so
        the request's remaining/out are only ever touched by the delivery
        thread, in pipeline order. ``cancel_event`` (armed by the
        protocol front-end on disconnect/stream cancel) is polled here —
        between decode steps — so an abandoned generation frees its slot
        even when its response generator never runs again. Returns the
        slots released (a head-of-line request that went away held
        none)."""
        slots = []
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.abandoned:
                slots.append(slot)
                # Pages back BEFORE the slot reads empty: anything polling
                # _slot_req for completion (tests, warm_admission callers)
                # must find the pool already reconciled.
                self._prefilling.pop(slot, None)
                self._free_slot_blocks(slot)
                self._slot_req[slot] = None
                self._dist.submit_cancel(req)
        if self._pending is not None and self._pending.abandoned:
            self._pending.end(None, _stepscope.OUTCOME_CANCELLED)
            self._pending = None
        return slots

    def _process_frees(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Apply slot-completions reported by the delivery thread.

        Only the engine loop mutates slot state; the distributor just
        queues the (slot, req) pairs of a dispatch here when their final
        tokens went out. Pages return to the pool HERE — block-granular,
        the moment the request finishes, not when the slot's longest
        cohabitant does. Returns the slots freed.
        """
        slots = []
        while True:
            try:
                finished = self._dist.free_q.get_nowait()
            except queue.Empty:
                return slots
            for slot, req in finished:
                if self._slot_req[slot] is req:
                    slots.append(slot)
                    # Pages back BEFORE the slot reads empty (same
                    # ordering as _release_cancelled: pollers of _slot_req
                    # must find the pool already reconciled).
                    self._free_slot_blocks(slot)
                    self._slot_req[slot] = None

    def _housekeep(self) -> bool:  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Frees, cancels and admissions, in that order: what the loop does
        between dispatches besides waiting. The slots the pass freed are
        reset on the device by ONE update, enqueued here: before
        ``_admit_requests`` can hand their pages on, and before the pass's
        next model dispatch (an old bank's surplus decode writes already
        in flight land in those pages BEFORE anything the next holder
        writes there, and no later dispatch reads the old rows). When the
        pass did something the stretch is stepscope's ``admit`` loop state;
        returns whether it did."""
        began = _stepscope.clock()
        freed = self._process_frees() + self._release_cancelled()
        if freed:
            self._write_slot_state(self._tokens, frees=freed)
        admitted = self._admit_requests()
        worked = bool(freed) or admitted
        if worked:
            _stepscope.loop_state(self._scope_name, _stepscope.LOOP_ADMIT,
                                  began, _stepscope.clock(),
                                  self.max_slots)
        return worked

    def _admit_requests(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Claim free slots for queued requests: reserve pages (admission
        gates on FREE PAGES now, not just free slots) and queue the
        chunked prefill. No compute happens here — chunks dispatch from
        _advance_prefills, interleaved with decode steps. Returns whether
        a request was admitted (or turned away)."""
        took = False
        for slot in range(self.max_slots):
            if self._slot_req[slot] is not None:
                continue
            req = self._pending
            self._pending = None
            if req is None:
                try:
                    req = self._admit.get_nowait()
                except queue.Empty:
                    return took
            if req.abandoned:
                req.end(None, _stepscope.OUTCOME_CANCELLED)
                took = True
                continue
            st = self._reserve(req)
            if isinstance(st, BaseException):
                req.end(st, _stepscope.OUTCOME_ERROR)
                took = True
                continue
            if st is None:
                # Pool exhausted: hold the head of the line (FIFO — no
                # starvation by smaller latecomers) and retry when a
                # completion returns pages.
                self._pending = req
                if req.span is not None:
                    req.span.waited_for_pages = True
                return took
            took = True
            if req.span is not None:
                req.span.admitted_ns = time.monotonic_ns()
            self._slot_req[slot] = req
            self._slot_blocks[slot] = st.blocks
            self._prefilling[slot] = st
        return took

    def _advance_prefills(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        """Dispatch ONE prefill chunk for every still-prefilling slot —
        all slots in a SINGLE batched dispatch — then admit completed
        ones into the decode bank by one slot-state update. One
        chunk per slot per loop top is the interleave: decode steps run
        between chunks, so a long prompt streams in without stalling
        anyone's ITL. Batching the chunks across slots is the
        TTFT-under-churn term: batched steps finish batchmates together,
        their clients resubmit together, and K serial chunk dispatches
        would put k×chunk-time in front of every admission in the burst
        (measured: the serial form put the c8 TTFT p99 at ~4× c1's on
        the CPU reference host; batched, the burst costs ~one chunk).
        Returns whether a chunk was dispatched.
        """
        if not self._prefilling:
            return False
        active = sorted(self._prefilling)
        c = self.prefill_chunk
        n_real = len(active)
        # Lane count bucketed to a power of two (≤ max_slots buckets
        # total): pad lanes carry an all-scratch table, n_valid=1, and
        # temp 0, so their writes land on the scratch page and their
        # greedy "first token" is discarded.
        kk = _pow2_bucket(n_real, self.max_slots)
        chunks = np.zeros((kk, c), np.int32)
        starts = np.zeros((kk,), np.int32)
        n_valids = np.ones((kk,), np.int32)
        seeds = np.zeros((kk,), np.int32)
        temps = np.zeros((kk,), np.float32)
        topks = np.zeros((kk,), np.int32)
        # Context extent: a chunk's valid rows only index blocks below
        # ceil((start + n_valid) / bs), and the causal mask admits no
        # key past the last valid position — so the table (and with it
        # the gather + attention-key extent inside the kernel, which
        # derives everything from btabs.shape) truncates losslessly to
        # the longest prompt-so-far in the batch. Bucketed to a power
        # of two: one compiled shape per (lane, context) bucket instead
        # of every chunk paying a max_len-wide gather, which on the
        # contiguous-workload gate cost more per 32-token chunk than a
        # whole batched decode step.
        needed = 1
        lanes = []  # (slot, st, start, n_valid)
        for slot in active:
            st = self._prefilling[slot]
            start = st.next
            n_valid = min(c, st.prompt_len - start)
            lanes.append((slot, st, start, n_valid))
            needed = max(
                needed, -(-(start + n_valid) // self.block_size)
            )
        n_ctx = (_pow2_bucket(needed, self._max_blocks)
                 if self._model.prefill_by_context else self._max_blocks)
        btab_rows = np.zeros((kk, self._ring + n_ctx), np.int32)
        for i, (slot, st, start, n_valid) in enumerate(lanes):
            chunks[i, :n_valid] = st.req.prompt[0, start:start + n_valid]
            starts[i] = start
            n_valids[i] = n_valid
            seeds[i] = st.req.seed
            temps[i] = st.req.temperature
            topks[i] = st.req.top_k
            btab_rows[i] = self._table_row(slot, st.blocks, n_ctx)
        # No dispatch ticket for prefill chunks: admissions are bounded
        # by the slot count, and blocking a NEW request's prefill on a
        # step-readback ticket is the TTFT-under-load term.
        scope = _stepscope.step_begin(
            self._scope_name, _stepscope.PHASE_PREFILL_CHUNK,
            self._prefill_seq, batch_size=n_real, slots=self.max_slots,
            lanes=kk, ctx_blocks=n_ctx,
        )
        if scope is not None:
            # Positions computed, and the context the real lanes hold
            # once this chunk is in (what the chunk's rows attend), in
            # tokens and in table entries.
            scope.tokens = sum(n for _, _, _, n in lanes)
            scope.ctx_tokens = sum(s + n for _, _, s, n in lanes)
            self._note_attention(
                scope, [(s + n, n) for _, _, s, n in lanes], kk * n_ctx,
                active, rows_per_table=c)
        self._prefill_seq += 1
        # One compile-cache entry per (lane, context) bucket: the key is
        # the traced-shape identity XLA uses, so the retrace counter and
        # the tpusan bucket-budget watcher see exactly what XLA compiles.
        _stepscope.note_compile(
            self._scope_name, "prefill_chunk", f"{kk}x{c}x{n_ctx}"
        )
        (firsts_dev,), extras = self._keep_pools(self._prefill_chunk_fn(
            self.params, *self._pools, jnp.asarray(chunks),
            jnp.asarray(btab_rows), jnp.asarray(starts),
            jnp.asarray(n_valids), jnp.asarray(seeds),
            jnp.asarray(temps), jnp.asarray(topks),
        ), 1)
        _stepscope.step_dispatched(scope)
        _stepscope.charge_collectives(scope, self._expected_collectives)
        # The dispatch return, on the requests' timelines too.
        returned_ns = scope.t_dispatch if scope is not None else 0
        done = []  # (lane, slot, state)
        for i, (slot, st, start, n_valid) in enumerate(lanes):
            st.next = start + n_valid
            span = st.req.span
            if span is not None:
                returned_ns = returned_ns or time.monotonic_ns()
                if not span.chunks:
                    span.first_chunk_ns = returned_ns
                span.last_chunk_ns = returned_ns
                span.chunks += 1
            if st.next >= st.prompt_len:
                done.append((i, slot, st))
        if done:
            try:
                firsts_dev.copy_to_host_async()
            except AttributeError:
                pass
        _stepscope.step_end(scope, outputs=firsts_dev)
        self._dist.submit_routing(scope, extras)
        if not done:
            return True
        # stepscope's ``join`` loop state: from here to the hand-over of
        # the first tokens.
        joining_from = _stepscope.clock() if scope is not None else None
        # A synchronized churn burst (batched steps finish batchmates
        # together, their clients resubmit together) completes many
        # prefills at one loop top. Whatever their number, the burst is
        # ONE slot-state update, enqueued here: after the last chunk's
        # dispatch and before the decode dispatch that first includes the
        # slots. Setting the DEVICE block-table row only now is what
        # routes a slot's decode writes from the scratch page onto its
        # real pages; the slot's first token is taken from the chunk's
        # result inside the update. And ONE first-token delivery, of the
        # chunk's whole result with each pair's index its lane — k
        # separate prio deliveries would re-pay the fixed per-readback
        # cost k times on the delivery thread. Admission never blocks on a
        # readback; order per request is preserved (the prio entry
        # precedes any step including these slots).
        for _, slot, st in done:
            del self._prefilling[slot]
            # First token counts against the budget: decode dispatches
            # owe max_new - 1 more (the fuse chooser reads this).
            self._dispatched[slot] = 1
            for i in range(st.n_hit, len(st.hashes)):
                self._prefix.register(st.hashes[i], st.blocks[i])
        self._write_slot_state(firsts_dev, joins=done)
        self._dist.submit(
            firsts_dev, [(lane, slot, st.req) for lane, slot, st in done],
            first_token=True, scope=scope,
        )
        _stepscope.loop_state(self._scope_name, _stepscope.LOOP_JOIN,
                              joining_from, _stepscope.clock(),
                              self.max_slots)
        return True

    def warm_admission(self):
        """Run the slot-state update once with nothing joined and nothing
        freed, so its executable is loaded before a serving window (the
        update has one shape whatever a burst carries; ``warm_prefill``
        makes it for each lane bucket of a prefill's result). The state
        keeps its values, but the vectors are rebound, so idleness is
        enforced under the cv instead of being a docstring contract
        (ADVICE r5 #1).

        The call runs UNDER ``self._cv``: an actively-serving engine
        (occupied slots or queued admissions) raises, and holding the cv
        for the duration excludes concurrent ``submit()``s — an
        alive-but-idle engine thread is then harmless, since its loop
        only mutates slot state in response to admissions, frees, or
        cancels, none of which can arrive while the cv is held. (The
        idle loop itself blocks on this cv, so it cannot even re-check.)
        """
        with self._cv:
            self._require_idle("warm_admission")
            self._write_slot_state(self._tokens)
            jax.block_until_ready(self._tokens)

    def _require_idle(self, what: str):  # tpulint: disable=TPU002,TPU009 - both callers hold self._cv
        """Raise unless nothing is served or queued (under ``self._cv``)."""
        if self._stopping or self._broken is not None:
            raise RuntimeError(f"{what} on a stopped or broken engine")
        busy = [s for s, r in enumerate(self._slot_req) if r is not None]
        if busy or not self._admit.empty() or self._pending is not None:
            raise RuntimeError(
                f"{what} requires an idle engine: all slots "
                "free and an empty admission queue (busy slots: "
                f"{busy}, queued admissions: {self._admit.qsize()})"
            )

    def warm_prefill(self, ctx_blocks=(1,)):
        """Compile the chunk-prefill shape family — every power-of-two
        lane bucket × the power-of-two context buckets covering
        ``ctx_blocks`` (block counts, e.g. ceil(prompt_len/block_size)
        for each prompt length a serving window will carry) — so no
        multi-second XLA compile lands inside a measured window when a
        synchronized churn burst first produces that batch shape. Warm
        lanes carry all-scratch tables, so every write routes to the
        scratch page and no pool pages are touched. Each lane bucket's
        prefill is followed by the slot-state update on that prefill's
        own result (nothing joined: the state keeps its values), so the
        update is compiled for an array placed as a window's will be. Same
        idle-only contract as ``warm_admission`` (the chunk fn donates
        the pools, so it must not race the engine loop's own
        dispatches)."""
        with self._cv:
            self._require_idle("warm_prefill")
            c = self.prefill_chunk
            buckets = sorted(
                {_pow2_bucket(max(1, int(b)), self._max_blocks)
                 for b in ctx_blocks}
            ) if self._model.prefill_by_context else [self._max_blocks]
            kk = 1
            while True:
                for n_ctx in buckets:
                    z = jnp.zeros((kk,), jnp.int32)
                    (firsts,), _ = self._keep_pools(self._prefill_chunk_fn(
                        self.params, *self._pools,
                        jnp.zeros((kk, c), jnp.int32),
                        jnp.zeros((kk, self._ring + n_ctx), jnp.int32),
                        z, jnp.ones((kk,), jnp.int32), z,
                        jnp.zeros((kk,), jnp.float32), z,
                    ), 1)
                self._write_slot_state(firsts)
                if kk >= self.max_slots:
                    break
                kk = min(kk * 2, self.max_slots)
            jax.block_until_ready(self._pools[0])

    def _run(self):  # tpulint: disable=TPU002,TPU009 - engine-loop thread is the sole mutator of slot state
        try:
            self._run_loop()
        except BaseException as e:  # noqa: BLE001 — engine must not die silently
            _stepscope.step_abandon()  # a dispatch that raised left it open
            # The jits donate the cache pool: after a failed dispatch the
            # engine cannot be restarted against possibly-deleted buffers.
            # Mark broken (submit() refuses), surface the error to every
            # waiting consumer (their generators re-raise it), and stop.
            with self._cv:
                self._broken = e
            try:
                # Best-effort: let in-flight deliveries land before the
                # error terminators so consumers see tokens-then-error,
                # not interleaved queues from two live threads.
                self._dist.drain_and_stop(timeout=5.0)
            except Exception:
                pass
            failed = _stepscope.OUTCOME_ERROR
            if self._pending is not None:
                self._pending.end(e, failed)
                self._pending = None
            while True:
                try:
                    self._admit.get_nowait().end(e, failed)
                except queue.Empty:
                    break
            for slot, req in enumerate(self._slot_req):
                if req is not None:
                    req.end(e, failed)
                    self._slot_req[slot] = None
                    self._prefilling.pop(slot, None)
                    # Host bookkeeping only: the device is suspect.
                    self._free_slot_blocks(slot)

    # tpulint: hot-path
    def _run_loop(self):  # tpulint: disable=TPU002,TPU009,TPU011 - engine loop is the sole mutator of slot state AND the sole _cv waiter: it cannot sleep across its own updates
        # Software pipeline with DECOUPLED delivery: steps and admissions'
        # prefill chunks dispatch with DEVICE tokens; the delivery thread
        # drains readbacks FIFO behind them (at most max_inflight
        # dispatches ahead). Scheduling depends on token COUNTS, never
        # values, so delivery may lag compute. The engine loop itself
        # never blocks on a host copy — an arriving request's first
        # prefill chunk dispatches at the very next loop top regardless
        # of in-flight readbacks, which is what bounds TTFT under load
        # (VERDICT r4 #4).
        step_seq = 0  # host-side decode-step index (stepscope records)
        while True:
            # Lock-free polls of monotonic signal flags: the loop re-checks
            # every iteration, so the worst race is one extra step.
            if self._stopping:  # tpulint: disable=TPU002,TPU009 - single-transition stop/broken flags polled lock-free by the loop
                self._dist.drain_and_stop()
                self._process_frees()
                self._drain_terminated()
                return
            broken = self._broken  # tpulint: disable=TPU002,TPU009 - single-transition stop/broken flags polled lock-free by the loop
            if broken is not None:
                raise broken
            self._housekeep()
            self._advance_prefills()
            active = [s for s, r in enumerate(self._slot_req)
                      if r is not None and s not in self._prefilling]
            if not active:
                if self._prefilling:
                    continue  # keep streaming chunks in
                with self._cv:
                    if (self._admit.empty() and self._dist.free_q.empty()
                            and self._pending is None):
                        # stepscope's ``idle_wait`` loop state.
                        began = _stepscope.clock()
                        got = self._cv.wait(timeout=5.0)
                        _stepscope.loop_state(
                            self._scope_name, _stepscope.LOOP_IDLE_WAIT,
                            began, _stepscope.clock(), self.max_slots)
                        if (not got and self._admit.empty()
                                and self._dist.free_q.empty()
                                and self._pending is None):
                            # Idle: park the engine; submit() restarts it.
                            # (The delivery thread parks itself on its
                            # queue; in-flight readbacks still complete.)
                            self._thread = None
                            return
                continue
            # Wait for a step ticket WITHOUT starving admissions: a new
            # request's prefill chunks are ticket-exempt and must dispatch
            # while the step pipeline is full, or TTFT under load degrades
            # to a step-readback wait.
            # stepscope's ``ticket_wait`` loop state: from the first try
            # that misses until the ticket comes. Work done between tries
            # (an admission, a chunk dispatch) closes the stretch before
            # it and opens another after, so no two records overlap.
            waiting_from = _stepscope.clock()
            missed = False
            got_ticket = self._dist.try_ticket(timeout=0.005)
            while not got_ticket:
                # Same lock-free signal poll as the loop top.
                if self._stopping or self._broken is not None:  # tpulint: disable=TPU002,TPU009 - single-transition stop/broken flags polled lock-free by the loop
                    break
                missed = True
                missed_at = _stepscope.clock() if waiting_from else None
                worked = self._housekeep()
                dispatched = self._advance_prefills()
                if (worked or dispatched) and waiting_from:
                    _stepscope.loop_state(
                        self._scope_name, _stepscope.LOOP_TICKET_WAIT,
                        waiting_from, missed_at, self.max_slots)
                    waiting_from = _stepscope.clock()
                got_ticket = self._dist.try_ticket(timeout=0.005)
            if missed:
                _stepscope.loop_state(
                    self._scope_name, _stepscope.LOOP_TICKET_WAIT,
                    waiting_from, _stepscope.clock(), self.max_slots)
            if not got_ticket:
                continue  # stopping/broken handled at loop top
            # Recompute: slots whose prefill completed during the ticket
            # wait join this very step (their pages + token state are
            # live) — and every occupant may have finished/cancelled
            # during the wait, in which case the ticket goes back unspent
            # instead of dispatching a whole-bank step over garbage.
            active = [s for s, r in enumerate(self._slot_req)
                      if r is not None and s not in self._prefilling]
            if not active:
                self._dist.release_ticket()
                continue
            fuse = self._choose_fuse(active)
            scope = _stepscope.step_begin(
                self._scope_name, _stepscope.PHASE_DECODE, step_seq,
                batch_size=len(active), slots=self.max_slots,
                lanes=self.max_slots, ctx_blocks=self._max_blocks,
            )
            if scope is not None:
                scope.micro_steps = fuse
                scope.tokens = len(active) * fuse
                # Context held by the active slots as the dispatch's first
                # micro-step sees it: prompt + tokens dispatched so far.
                held = [self._slot_req[s].prompt.shape[1]
                        + self._dispatched[s] for s in active]
                scope.ctx_tokens = sum(held)
                # Table entries under the active slots' lengths, every
                # micro-step's: what a paged kernel visits. A gathering
                # family takes, for every slot of the bank, the width over
                # the longest of them, a micro-step later a position more.
                self._note_attention(
                    scope, [(n + i, 1) for n in held for i in range(fuse)],
                    self.max_slots * sum(
                        self._model.pages_gathered(
                            max(held) + i, self._max_blocks, self.block_size)
                        for i in range(fuse)), active)
            step_seq += fuse
            # Whole-bank decode traces one shape per fuse width: the
            # unfused branch is a single cache entry, the fused branch
            # one per distinct window (bounded by the fuse policy).
            _stepscope.note_compile(
                self._scope_name, "decode_step",
                f"bank:{self.max_slots}x{self._max_blocks}:fuse:{fuse}",
            )
            if fuse == 1:
                (toks,), extras = self._keep_pools(self._step(
                    self.params, *self._pools, self._btabs,
                    self._tokens, self._pos, self._seeds, self._steps,
                    self._temps, self._topks,
                ), 1)
                self._tokens = toks
                self._pos, self._steps = self._advance(
                    self._pos, self._steps
                )
            else:
                # Fused window: one dispatch, [fuse, S] tokens, carry
                # advanced on device (no per-step host enqueues).
                (toks, self._tokens, self._pos,
                 self._steps), extras = self._keep_pools(
                    self._multi_step_fn(fuse)(
                        self.params, *self._pools, self._btabs,
                        self._tokens, self._pos, self._seeds, self._steps,
                        self._temps, self._topks,
                    ), 4)
            _stepscope.step_dispatched(scope)
            if scope is not None:
                ops = self._expected_collectives if fuse == 1 else {
                    op: c * fuse
                    for op, c in self._expected_collectives.items()
                }
                _stepscope.charge_collectives(scope, ops)
            try:
                toks.copy_to_host_async()
            except AttributeError:
                pass
            for s in active:
                self._dispatched[s] += fuse
            self._dist.submit(
                toks, [(s, s, self._slot_req[s]) for s in active
                       if self._slot_req[s] is not None],
                scope=scope,
            )
            _stepscope.inflight_update(self._scope_name, 1)
            # sync mode blocks on the step output here (true device time,
            # at the cost of the host/device overlap); counters mode only
            # stamps the clock.
            _stepscope.step_end(scope, outputs=toks)
            self._dist.submit_routing(scope, extras)


class GptEngineModel(Model):
    """`gpt` served through the continuous-batching engine.

    Same wire contract as GptModel (INPUT_IDS [1, L], optional MAX_TOKENS,
    one OUTPUT_IDS response per token) — but concurrent requests share
    batched decode steps instead of running private generation loops,
    over a paged KV block pool with chunked prefill and prefix caching.
    """

    name = "gpt_engine"
    platform = "jax"
    decoupled = True
    blocking = True
    # The core injects the request's cancel_event (PARAM_CANCEL_EVENT in
    # the parameters copy) so the engine can poll it between decode steps,
    # and beside it the request's TraceContext timeline
    # (PARAM_TRACE_TIMESTAMPS), which stepscope copies its receipt stamps
    # from.
    accepts_cancel_event = True

    def __init__(self, cfg: Optional[GptConfig] = None, seed: int = 0,
                 max_slots: int = 8, mesh=None, block_size: int = 16,
                 n_blocks: Optional[int] = None, prefill_chunk: int = 32):
        super().__init__()
        self.cfg = cfg or gpt_small()
        self.inputs, self.outputs = wire_tensors()
        key = jax.random.PRNGKey(seed)
        if mesh is not None:
            # Initialize DIRECTLY sharded — no single-device staging copy
            # (parallel/sharding.init_sharded).
            from tritonclient_tpu.models.gpt import PARTITION_RULES
            from tritonclient_tpu.parallel.sharding import init_sharded

            params = init_sharded(
                mesh, lambda k: init_params(k, self.cfg),
                PARTITION_RULES, key,
            )
        else:
            params = init_params(key, self.cfg)
        # mesh: tensor-parallel engine (KV block pool sharded; pre-sharded
        # params pass through shard_tree as a no-op).
        self.engine = GenerationEngine(self.cfg, params,
                                       max_slots=max_slots, mesh=mesh,
                                       scope_name=self.name,
                                       block_size=block_size,
                                       n_blocks=n_blocks,
                                       prefill_chunk=prefill_chunk)

    def estimate_request_bytes(self, input_shapes):
        """KV page reservation this request will hold: the engine's
        admission formula ``ceil((prompt + max_new) / block_size)``
        pages, reckoned by kind through the family (a window layer holds
        its ring's pages and no more; max_new estimated at infer's default
        of 16 — MAX_TOKENS data is not resolved at stamp time).
        """
        shape = input_shapes.get("INPUT_IDS")
        if not shape:
            return None
        length = int(shape[-1])
        e = self.engine
        n = min(-(-(length + 16) // e.block_size), e._max_blocks)
        return int(sum(e.held_bytes(n)))

    def infer(self, inputs, parameters=None) -> Iterator[dict]:
        prompt = np.asarray(inputs["INPUT_IDS"], dtype=np.int32)
        if prompt.ndim == 1:
            prompt = prompt.reshape(1, -1)
        if prompt.ndim != 2 or prompt.shape[0] != 1:
            raise ValueError(
                "gpt_engine serves one [1, L] (or [L]) sequence per "
                "request (batching happens ACROSS requests in the "
                f"engine); got shape {list(prompt.shape)}"
            )
        if prompt.shape[1] >= self.cfg.max_len:
            raise ValueError(
                f"prompt length {prompt.shape[1]} must be < max_len "
                f"{self.cfg.max_len} to generate at least one token"
            )
        max_new = 16
        if "MAX_TOKENS" in inputs:
            max_new = int(np.asarray(inputs["MAX_TOKENS"]).flatten()[0])
        temperature, top_k, gen_seed = sampling_inputs(inputs)
        from tritonclient_tpu.protocol._literals import (
            PARAM_CANCEL_EVENT,
            PARAM_TRACE_TIMESTAMPS,
        )

        cancel_event = (parameters or {}).get(PARAM_CANCEL_EVENT)
        timestamps = (parameters or {}).get(PARAM_TRACE_TIMESTAMPS)

        def gen():
            # Admission happens on FIRST consumption (not at infer()):
            # a transport that abandons the response generator before
            # ever starting it (pipelined requests + client disconnect)
            # then never occupies a slot at all. The finally hook covers
            # the started case: GeneratorExit on the draining transport
            # marks the request cancelled so the engine frees the slot
            # instead of generating dead tokens to max_new (advisor r3).
            req = self.engine.submit(prompt, max_new,
                                     temperature=temperature,
                                     top_k=top_k, seed=gen_seed,
                                     cancel_event=cancel_event,
                                     timestamps=timestamps)
            # stepscope's two stamps a token on this thread (None while it
            # is off): the handler holds the token; the transport has sent
            # it and asks for the next.
            span = req.span
            try:
                while True:
                    token = req.out.get(timeout=300)
                    if token is None:
                        return
                    if isinstance(token, BaseException):
                        raise token
                    if span is not None:
                        span.taken_ns.append(time.monotonic_ns())
                    yield {"OUTPUT_IDS": token}
                    if span is not None:
                        span.resumed_ns.append(time.monotonic_ns())
            finally:
                req.cancelled = True

        return gen()

    def warmup(self):
        q = self.engine.submit(np.zeros((1, 8), np.int32), 2).out
        while q.get(timeout=300) is not None:
            pass
