"""Host-side bookkeeping for the paged KV cache (block pool + prefix cache).

The gpt engine's KV memory is a fixed pool of ``[n_layers, n_blocks,
block_size, H * Dh]`` pages on device (models/gpt_engine.py); THIS module
owns the host-side allocation state around it:

  * ``BlockPool`` — a free list plus per-block reference counts. Block 0
    is reserved by the engine as the SCRATCH page (garbage writes from
    idle/prefilling slots route there — in a paged layout a stray write
    into a reallocated block would corrupt another request's KV, which
    the old contiguous bank never had to worry about).
  * ``PrefixCache`` — completed FULL prompt blocks keyed by a cumulative
    token hash (vLLM-style prompt caching). A hit bumps the block's
    refcount and resolves to a block-table entry instead of recompute;
    blocks whose refcount drops to zero stay cached on an LRU list and
    are evicted only when the pool would otherwise fail an allocation.
    Shared blocks are always full, so decode never writes into them —
    no copy-on-write needed.

Both structures take their locks through ``sanitize.named_lock`` so the
tpusan lock-order witness sees them; in practice the engine loop is the
sole caller, the locks guard the /metrics snapshot path. Acquisition
order is PrefixCache -> BlockPool (the cache calls into its pool).

A module-level registry lets ``server/_core.prometheus_metrics`` render
``nv_engine_kv_blocks_used`` / ``nv_engine_kv_blocks_total`` gauges and
the ``nv_engine_prefix_cache_events_total{model,event}`` counter without
importing the (heavy) model zoo: engines register a snapshot callable
here at construction. This module is dependency-free (no jax/numpy).

Both structures additionally report page grants/frees/parks/evictions
into the memscope byte ledger (``tritonclient_tpu._memscope``) once an
engine attaches its identity via :func:`attach_memscope` — every hook
is branch-only until then (and branch-only inside memscope when the
ledger is off).
"""

import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

from tritonclient_tpu import _memscope, sanitize
from tritonclient_tpu.protocol._literals import (
    PREFIX_EVENT_EVICT,
    PREFIX_EVENT_HIT,
    PREFIX_EVENT_MISS,
    PREFIX_EVENTS,
)

# /metrics family names (exposed by server/_core.prometheus_metrics and
# validated by scripts/check_metrics_exposition.py).
KV_BLOCKS_USED_METRIC = "nv_engine_kv_blocks_used"
KV_BLOCKS_TOTAL_METRIC = "nv_engine_kv_blocks_total"
PREFIX_EVENTS_METRIC = "nv_engine_prefix_cache_events_total"

# Hash-chain seed for block keys (any fixed odd constant; the chain just
# has to be deterministic across processes for tests).
_HASH_SEED = 0x9E3779B97F4A7C15


def block_hash(prev_hash: int, tokens) -> int:
    """Cumulative hash of one FULL block of prompt tokens.

    ``prev_hash`` chains the key over every earlier block, so equal keys
    imply equal full prefixes (modulo hash collision), never just equal
    block contents at different depths. Python's ``hash`` on tuples is
    salted per-process for str — ints are stable, but route through a
    deterministic mix anyway so dumps/tests can rely on values.
    """
    h = prev_hash ^ _HASH_SEED
    for t in tokens:
        h = (h * 1099511628211 + int(t) + 1) & 0xFFFFFFFFFFFFFFFF
    return h


class BlockPool:
    """Free list + refcounts over ``n_blocks`` KV pages.

    Invariants (checked in tests, not at runtime):
      * every block id is in exactly one of: free list, evictable LRU
        (owned by a PrefixCache), or referenced (``refcount > 0``);
      * ``free`` on a block whose refcount is already zero raises —
        double-frees corrupt the pool silently otherwise.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 2:
            raise ValueError(
                f"block pool needs >= 2 blocks (scratch + 1), got {n_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._lock = sanitize.named_lock("kvcache.BlockPool")
        # Pop order: lowest id first (so the engine's init alloc of the
        # scratch page deterministically gets block 0).
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._ref: List[int] = [0] * n_blocks
        # (scope, block_bytes) once attach_memscope binds this pool to a
        # ledger row; None keeps every hook branch-only.
        self._ms: Optional[Tuple[str, int]] = None

    # -- allocation ---------------------------------------------------------

    def try_alloc(self) -> Optional[int]:
        """Pop a free block (refcount 1) or None if the free list is empty."""
        with self._lock:
            if not self._free:
                return None
            bid = self._free.pop()
            self._ref[bid] = 1
            if self._ms is not None:
                _memscope.kv_page_alloc(self._ms[0], self._ms[1])
            return bid

    def ref(self, bid: int) -> None:
        """Add a reference to an already-allocated (or evictable) block."""
        with self._lock:
            self._ref[bid] += 1

    def unref(self, bid: int) -> bool:
        """Drop one reference; returns True when the count hit zero.

        The CALLER decides where a zero-ref block goes: ``release`` (back
        to the free list) or a PrefixCache's evictable LRU.
        """
        with self._lock:
            if self._ref[bid] <= 0:
                raise RuntimeError(
                    f"double-free of KV block {bid} (refcount already 0)"
                )
            self._ref[bid] -= 1
            return self._ref[bid] == 0

    def release(self, bid: int) -> None:
        """Return a zero-ref block to the free list."""
        with self._lock:
            if self._ref[bid] != 0:
                raise RuntimeError(
                    f"release of KV block {bid} with refcount "
                    f"{self._ref[bid]} (must be 0)"
                )
            self._free.append(bid)
            if self._ms is not None:
                _memscope.kv_page_free(self._ms[0], self._ms[1])

    # -- introspection ------------------------------------------------------

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_count(self) -> int:
        """Blocks held by live references (scratch included — honest)."""
        with self._lock:
            return sum(1 for r in self._ref if r > 0)

    def refcount(self, bid: int) -> int:
        with self._lock:
            return self._ref[bid]


class PrefixCache:
    """Hash-keyed cache of completed full prompt blocks over a BlockPool.

    ``match`` resolves one cumulative block hash to a cached block id
    (refcounted share) or records a miss; ``register`` publishes a block
    this request just prefilled; ``release_block`` routes a zero-ref
    block to the evictable LRU (registered) or back to the pool's free
    list (not registered); ``evict_lru`` reclaims the least-recently-
    released cached block when an allocation would otherwise fail.
    """

    def __init__(self, pool: BlockPool):
        self._pool = pool
        self._lock = sanitize.named_lock("kvcache.PrefixCache")
        self._by_hash: Dict[int, int] = {}
        self._hash_of: Dict[int, int] = {}
        # hash -> bid for blocks with refcount 0 (LRU order: oldest first).
        self._evictable: "OrderedDict[int, int]" = OrderedDict()
        self.events: Dict[str, int] = {e: 0 for e in PREFIX_EVENTS}
        self._ms: Optional[Tuple[str, int]] = None

    def match(self, hash_key: int) -> Optional[int]:
        """Look up one cumulative block hash; refs and returns the block
        on a hit (removing it from the evictable LRU if parked there).

        Does NOT count hit/miss events: a reservation that later fails
        (pool exhausted) rolls back and retries, and counting per probe
        would inflate the hit rate with every blocked-admission retry.
        The engine counts once per COMMITTED admission via ``count``.
        """
        with self._lock:
            bid = self._by_hash.get(hash_key)
            if bid is None:
                return None
            unparked = hash_key in self._evictable
            if unparked:
                del self._evictable[hash_key]
            self._pool.ref(bid)
            if self._ms is not None:
                _memscope.kv_page_grant_shared(
                    self._ms[0], self._ms[1], unparked)
            return bid

    def count(self, event: str, n: int = 1) -> None:
        """Record ``n`` occurrences of one canonical prefix-cache event."""
        with self._lock:
            self.events[event] += n

    def register(self, hash_key: int, bid: int) -> None:
        """Publish a freshly-prefilled FULL block under its chain hash.

        First writer wins: if another request already published this
        hash, the newcomer's block simply stays unregistered (it returns
        to the free list when its request finishes).
        """
        with self._lock:
            if hash_key not in self._by_hash and bid not in self._hash_of:
                self._by_hash[hash_key] = bid
                self._hash_of[bid] = hash_key

    def release_block(self, bid: int) -> None:
        """Drop one reference; a zero-ref registered block parks on the
        evictable LRU (its KV stays warm), an unregistered one goes back
        to the pool's free list."""
        with self._lock:
            if not self._pool.unref(bid):
                # Still shared: residency unchanged, but THIS holder's
                # reservation is discharged.
                if self._ms is not None:
                    _memscope.kv_page_drop_shared(self._ms[0], self._ms[1])
                return
            h = self._hash_of.get(bid)
            if h is not None:
                self._evictable[h] = bid
                self._evictable.move_to_end(h)
                if self._ms is not None:
                    _memscope.kv_page_park(self._ms[0], self._ms[1])
            else:
                self._pool.release(bid)

    def evict_lru(self) -> Optional[int]:
        """Reclaim the LRU zero-ref cached block: forget its hash, count
        the eviction, and return it ref'd (count 1) for the caller —
        or None when nothing is evictable."""
        with self._lock:
            if not self._evictable:
                return None
            h, bid = self._evictable.popitem(last=False)
            del self._by_hash[h]
            del self._hash_of[bid]
            self.events[PREFIX_EVENT_EVICT] += 1
            if self._ms is not None:
                _memscope.kv_page_evict(self._ms[0], self._ms[1])
                # The reclaimed page's pool round-trip must not be
                # billed to the requester's attribution bracket: the
                # free returns a CACHE page, not one of theirs (the
                # re-alloc below is theirs, and stays billed).
                _memscope.push_owner("")
                try:
                    self._pool.release(bid)
                finally:
                    _memscope.pop_owner()
            else:
                self._pool.release(bid)
            got = self._pool.try_alloc()
            # The free list pops lowest-id first; the block just released
            # is not guaranteed to be the one handed back — any free
            # block serves the caller equally.
            return got

    @property
    def evictable_count(self) -> int:
        with self._lock:
            return len(self._evictable)

    def snapshot_events(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.events)


def attach_memscope(pool: BlockPool, prefix: Optional[PrefixCache],
                    scope: str, block_bytes: int) -> None:
    """Bind a pool (and its prefix cache) to a memscope ledger row:
    subsequent page grants/frees/parks/evictions report into the
    ``(scope, "kv")`` cell at ``block_bytes`` per page, and the pool's
    capacity is declared so the headroom gauge has a denominator."""
    key = (scope, int(block_bytes))
    pool._ms = key
    if prefix is not None:
        prefix._ms = key
    _memscope.set_capacity(scope, _memscope.MEM_POOL_KV,
                           pool.n_blocks * int(block_bytes),
                           unit=int(block_bytes))


# -- /metrics registry ------------------------------------------------------
#
# Engines register a zero-arg snapshot callable returning
#   {"used": int, "total": int, "events": {event: count}}
# keyed by model name. Weakly referenced through the owner object so a
# dropped engine vanishes from /metrics instead of pinning memory;
# latest registration wins per name (tests build engines repeatedly).

_registry_lock = sanitize.named_lock("kvcache.registry")
_registry: Dict[str, Tuple["weakref.ref", Callable[[], Dict]]] = {}


def register(model_name: str, owner, snapshot: Callable[[], Dict]) -> None:
    with _registry_lock:
        _registry[model_name] = (weakref.ref(owner), snapshot)


def unregister(model_name: str, owner) -> None:
    with _registry_lock:
        entry = _registry.get(model_name)
        if entry is not None and entry[0]() is owner:
            del _registry[model_name]


def metrics_snapshot() -> List[Tuple[str, Dict]]:
    """[(model_name, {"used", "total", "events"})] for live engines,
    sorted by name for stable exposition order."""
    out = []
    with _registry_lock:
        # Prune dead refs at render time: a dropped engine must VANISH
        # from the exposition, not linger as a stale zero row (and the
        # registry must not grow unboundedly under test-driven engine
        # churn).
        for name in [n for n, (ref, _) in _registry.items()
                     if ref() is None]:
            del _registry[name]
        for name in sorted(_registry):
            ref, snap = _registry[name]
            if ref() is None:
                continue
            try:
                out.append((name, snap()))
            except Exception:
                continue
    return out
