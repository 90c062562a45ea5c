"""A grouped-query decoder whose layers are of two kinds, behind the paged
engine: WINDOW layers that attend the last ``window`` positions and GLOBAL
layers that attend them all, rotary positions, RMSNorm (on each head's q
and k too), a leading dense SwiGLU layer and then routed expert layers
with a shared expert, of which this program holds ONE CHIP'S SHARE.
``K-EXAONE-236B-A23B`` publishes this block; nothing here is specific to
its sizes.

The layer, per token ``x`` (every norm RMSNorm, no biases):

  * attention: ``q = x W_q`` -> H heads, ``k = x W_k``, ``v = x W_v`` ->
    H_kv heads; ``q``, ``k`` normalised over the head size, then rotated
    (the half-split rotation: ``[a, b] -> [a cos - b sin, b cos + a sin]``,
    in layers of both kinds); query head *i* reads K/V head
    ``i // (H / H_kv)``; scores ``q . k / sqrt(Dh)``, softmax over keys
    ``j <= i`` (global) or ``i - window < j <= i`` (window), ``. v``,
    ``W_o``.
  * dense layers: ``W_down(silu(x W_gate) * x W_up)``.
  * expert layers: ``mla_moe.route`` over all ``n_experts`` and
    ``mla_moe.routed_experts`` over the ``experts_held`` this program holds
    from ``first_expert`` on (one kernel, ``ops/grouped_experts.py``, that
    streams each held expert the tokens hit once; the seven pairs in eight
    whose expert another chip holds cost no product), plus the shared
    expert: one implementation for both families. The other chips of the
    layer, and the exchange with them, are not here and nothing stands in
    for them.

THE CACHE KNOWS THE KIND. Keys and values live in two flat pools ``[1,
pages, block_size, H_kv * Dh]`` of two regions. A global layer has
``n_blocks`` pages under the request's block table, as every family's
layers have: a request holds a page for every ``block_size`` positions. A
window layer has a RING of ``R`` pages a slot (``ring_pages``: the window
plus one prefill chunk, in pages, plus one), position *p* in ring page
``(p // block_size) % R``, whatever the request's length: its keys past
the window are overwritten, and no allocator is asked. The engine hands a
step one table row a slot, ``[ring entries | block table]``; entry 0 of
either region is a scratch page. Which region a layer's pages lie in, and
which plan of the attention kernel's grid is its own (the context's pages
or the window's), are DATA of the layer scan (a base page and a flag a
layer), so one scan body serves both kinds and the pools are its carry,
updated in place.

Prefix sharing is declined (``shares_prefix``): a hit hands over global
pages, and the window layers' keys of the last ``window`` positions before
the hit are in nobody's ring.
"""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tritonclient_tpu.models._base import Model
from tritonclient_tpu.models.gpt_engine import (
    GenerationEngine,
    GptEngineModel,
    PagedModel,
    wire_tensors,
)
from tritonclient_tpu.models.mla_moe import (
    _EXPERT_BANKS,
    _dot,
    _pick,
    _rms_norm,
    _swiglu,
    expert_banks,
    route,
    routed_experts,
    routing_counters,
)
from tritonclient_tpu.ops.paged_attention import (
    paged_attention,
    plan_pages,
    straight_line,
)

WINDOW, GLOBAL = "window", "global"


@dataclass(frozen=True)
class SwaMoeConfig:
    vocab_size: int = 153600
    d_model: int = 6144
    n_layers: int = 48             # the first ``n_dense_layers`` are dense
    n_dense_layers: int = 1
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 128
    layer_kinds: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL) * 12
    d_ff: int = 18432              # the dense layers' width
    n_experts: int = 128           # the router's width
    experts_held: int = 128        # this program's share of them ...
    first_expert: int = 0          # ... from this one on
    experts_per_token: int = 8
    d_expert: int = 2048
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_len: int = 16384           # positions served (the block table's width)
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_kinds) != self.n_layers or set(
                self.layer_kinds) - {WINDOW, GLOBAL}:
            raise ValueError(
                f"layer_kinds must name {self.n_layers} layers, each "
                f"{WINDOW!r} or {GLOBAL!r}: {self.layer_kinds}")
        if GLOBAL not in self.layer_kinds or WINDOW not in self.layer_kinds:
            raise ValueError("the family has layers of both kinds")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads are whole groups of K/V heads")
        if not 0 <= self.first_expert <= self.n_experts - self.experts_held:
            raise ValueError("the share lies within the router's experts")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_width(self) -> int:
        """A page row: every K/V head of one position."""
        return self.n_kv_heads * self.head_dim

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_kinds)


def swa_moe_tiny(max_len: int = 256, first_expert: int = 2) -> SwaMoeConfig:
    """Small config for tests and CPU runs (float32, so tolerances are
    tight): two periods, a window shorter than the test contexts, four
    query heads a K/V head, 8 experts of which 3 are held."""
    return SwaMoeConfig(
        vocab_size=256, d_model=64, n_layers=8, n_dense_layers=1, n_heads=8,
        n_kv_heads=2, head_dim=16, window=24,
        layer_kinds=(WINDOW, WINDOW, WINDOW, GLOBAL) * 2, d_ff=128,
        n_experts=8, experts_held=3, first_expert=first_expert,
        experts_per_token=2, d_expert=32, max_len=max_len,
        dtype=jnp.float32)


def init_params(key: jax.Array, cfg: SwaMoeConfig) -> Dict:
    """Seeded weights in the parameter layout the steps read: ``dense`` and
    ``moe`` hold their layers stacked, attention leaves in both; the expert
    matrices are the HELD experts' only."""
    d, dh = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(key, 40))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    def attention(n):
        return {
            "norm1": jnp.ones((n, d), cfg.dtype),
            "wq": dense((n, d, cfg.n_heads * dh), d),
            "wk": dense((n, d, cfg.kv_width), d),
            "wv": dense((n, d, cfg.kv_width), d),
            "q_norm": jnp.ones((n, dh), cfg.dtype),
            "k_norm": jnp.ones((n, dh), cfg.dtype),
            "wo": dense((n, cfg.n_heads * dh, d), cfg.n_heads * dh),
            "norm2": jnp.ones((n, d), cfg.dtype),
        }

    nd, nm, e = cfg.n_dense_layers, cfg.n_moe_layers, cfg.experts_held
    f, fe, fs = cfg.d_ff, cfg.d_expert, cfg.d_expert * cfg.n_shared_experts
    return {
        "embed": {"tok": dense((cfg.vocab_size, d), d)},
        "dense": dict(
            attention(nd),
            w_gate=dense((nd, d, f), d), w_up=dense((nd, d, f), d),
            w_down=dense((nd, f, d), f)),
        "moe": dict(
            attention(nm),
            router=dense((nm, d, cfg.n_experts), d),
            router_bias=0.05 * jax.random.normal(
                next(keys), (nm, cfg.n_experts), jnp.float32),
            w_gate=dense((nm, e, d, fe), d), w_up=dense((nm, e, d, fe), d),
            w_down=dense((nm, e, fe, d), fe),
            ws_gate=dense((nm, d, fs), d), ws_up=dense((nm, d, fs), d),
            ws_down=dense((nm, fs, d), fs)),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "head": dense((d, cfg.vocab_size), d),
    }


def _rope(x, positions, theta: float):
    """Rotate the halves ``[a, b]`` of the last axis by ``positions *
    theta ** (-2i / dim)``: ``[a cos - b sin, b cos + a sin]``. ``positions``
    broadcasts against ``x`` without its last axis."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class _Pages:
    """Where the pools' two regions lie and how a table row splits, for a
    pool of ``pages`` pages a ``max_slots`` bank with rings of ``ring``."""

    def __init__(self, cfg: SwaMoeConfig, pages: int, max_slots: int,
                 ring: int):
        self.ring = ring
        self.ring_region = 1 + max_slots * ring        # scratch, then rings
        n_global, n_window = cfg.layers_of(GLOBAL), cfg.layers_of(WINDOW)
        self.n_blocks, rest = divmod(
            pages - n_window * self.ring_region, n_global)
        if rest or self.n_blocks < 2:
            raise ValueError(f"a pool of {pages} pages is not {n_global} "
                             f"global regions and {n_window} ring regions "
                             f"of {self.ring_region}")
        # A layer's base page and kind, by layer index.
        seen = {GLOBAL: 0, WINDOW: 0}
        base = []
        for kind in cfg.layer_kinds:
            base.append(seen[kind] * self.n_blocks if kind == GLOBAL else
                        n_global * self.n_blocks
                        + seen[kind] * self.ring_region)
            seen[kind] += 1
        self.base = np.asarray(base, np.int32)
        self.is_global = np.asarray(
            [kind == GLOBAL for kind in cfg.layer_kinds])


def _scan_layers_over_kinds(params: Dict, x, k_pool, v_pool, pages: _Pages,
                            btabs, dest, off, positions, live, lengths,
                            rows: int, cfg: SwaMoeConfig):
    """Every paged step's layers: the dense layers, then the expert layers,
    two scans with ``(h, k_pool, v_pool)`` as the CARRY of both (never a
    scanned input or a stacked output: ``gpt_engine._scan_layers_over_pool``
    says what that costs; nor are the experts' matrices).

    x [N, d] with N = T * ``rows``; ``btabs`` [T, ring + n_ctx] are the
    table rows, each attended by ``rows`` consecutive rows of x. ``dest``
    and ``lengths`` are pairs (global, window): the page of each row's new
    K/V in a region (before the layer's base) and the length each row
    attends under; ``off``/``positions``/``live`` are per row. Returns (h,
    k_pool, v_pool, histograms [n_moe_layers, held + 1]).
    """
    n = x.shape[0]
    bs = k_pool.shape[2]
    group = cfg.n_heads // cfg.n_kv_heads
    eps, dh = cfg.rms_norm_eps, cfg.head_dim
    ring, table = btabs[:, :pages.ring], btabs[:, pages.ring:]
    n_ctx = table.shape[1]
    # The two plans of the kernel's grid, made once: a global layer's over
    # the block table and the whole context (a window as long as the table:
    # every key is live), a window layer's over the ring, entry e of its
    # table being ring page e % R.
    plans = (
        plan_pages(table, lengths[0], rows_per_table=rows, block_size=bs,
                   group=group, window=n_ctx * bs),
        plan_pages(ring[:, jnp.arange(n_ctx) % pages.ring], lengths[1],
                   rows_per_table=rows, block_size=bs, group=group,
                   window=cfg.window))

    def layer(ffn, carry, xs):
        h, k_pool, v_pool = carry
        lp, li, base, is_global = xs
        a = _rms_norm(h, lp["norm1"], eps)
        q = _dot(a, lp["wq"]).reshape(n, cfg.n_heads, dh)
        k = _dot(a, lp["wk"]).reshape(n, cfg.n_kv_heads, dh)
        q = _rope(_rms_norm(q, lp["q_norm"], eps), positions[:, None],
                  cfg.rope_theta)
        k = _rope(_rms_norm(k, lp["k_norm"], eps), positions[:, None],
                  cfg.rope_theta)
        # One scatter a pool at (page, offset) in the layer's own region,
        # then the kernel reads the pages its kind's plan names.
        page = base + jnp.where(is_global, dest[0], dest[1])
        k_pool = k_pool.at[0, page, off].set(
            k.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[0, page, off].set(
            _dot(a, lp["wv"]).astype(v_pool.dtype))
        plan = jax.tree.map(lambda g, w: jnp.where(is_global, g, w), *plans)
        out = paged_attention(
            q, k_pool, v_pool, 0, table,
            plan._replace(page_of=plan.page_of + base), rows_per_table=rows)
        h = h + _dot(out.astype(h.dtype).reshape(n, -1), lp["wo"])
        y, counts = ffn(_rms_norm(h, lp["norm2"], eps), lp, li)
        return (h + y, k_pool, v_pool), counts

    def dense_ffn(x, lp, li):
        return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None

    nd, moe = cfg.n_dense_layers, params["moe"]
    kinds = (jnp.asarray(pages.base), jnp.asarray(pages.is_global))
    carry, _ = lax.scan(
        lambda c, xs: layer(dense_ffn, c, xs), (x, k_pool, v_pool),
        (params["dense"], jnp.arange(nd)) + tuple(a[:nd] for a in kinds))
    # The experts' matrices are not scanned: the scan would slice (copy) a
    # layer's out of the stack; the grouped product takes the whole stack
    # and the layer's index (``routed_experts``).
    banks = expert_banks(moe)

    def moe_ffn(x, lp, li):
        experts, weights = route(x, lp["router"], lp["router_bias"], cfg)
        y, counts = routed_experts(x, experts, weights, live, banks, cfg, li)
        return (y + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
                counts)

    (x, k_pool, v_pool), counts = lax.scan(
        lambda c, xs: layer(moe_ffn, c, xs), carry,
        ({k: v for k, v in moe.items() if k not in _EXPERT_BANKS},
         jnp.arange(cfg.n_moe_layers)) + tuple(a[nd:] for a in kinds))
    return x, k_pool, v_pool, counts


def _head(params: Dict, x, cfg: SwaMoeConfig):
    x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# the three paged steps                                                       #
# --------------------------------------------------------------------------- #


def _decode_step_kinds(params: Dict, k_pool, v_pool, btabs, tokens, pos,
                       seeds, steps, temps, topks, cfg: SwaMoeConfig,
                       pages: _Pages):
    """One step for the whole slot bank; the arguments are
    ``gpt_engine._decode_step_paged``'s with a table row of ``[ring |
    block table]``. A slot whose block table starts at the scratch page (0)
    holds no request: it still advances, its K/V lands on the two scratch
    pages, and it is routed to no expert. Returns (next tokens [S], pools,
    histograms [n_moe_layers, held + 1])."""
    s_count = tokens.shape[0]
    bs = k_pool.shape[2]
    ring, table = btabs[:, :pages.ring], btabs[:, pages.ring:]
    x = params["embed"]["tok"][tokens]
    slots = jnp.arange(s_count)
    # Surplus pipeline steps can push pos past the reserved region; the
    # clamp keeps the (dropped-anyway) write inside the slot's own row.
    blk = jnp.minimum(pos // bs, table.shape[1] - 1)
    dest = (table[slots, blk], ring[slots, blk % pages.ring])
    x, k_pool, v_pool, counts = _scan_layers_over_kinds(
        params, x, k_pool, v_pool, pages, btabs, dest, pos % bs, pos,
        table[:, 0] > 0, (pos + 1, pos + 1), 1, cfg)
    nxt = _pick(_head(params, x, cfg), seeds, steps, temps, topks)
    return nxt, k_pool, v_pool, counts


def _decode_multi_step_kinds(params: Dict, k_pool, v_pool, btabs, tokens,
                             pos, seeds, steps, temps, topks,
                             cfg: SwaMoeConfig, pages: _Pages, n_steps: int):
    """``n_steps`` micro-steps in one dispatch: a scan over the single step
    (``gpt_engine._decode_multi_step_paged``). The histograms come back per
    micro-step."""

    def one(carry, _):
        tokens, pos, steps, k_pool, v_pool = carry
        nxt, k_pool, v_pool, counts = _decode_step_kinds(
            params, k_pool, v_pool, btabs, tokens, pos, seeds, steps, temps,
            topks, cfg, pages)
        return (nxt, pos + 1, steps + 1, k_pool, v_pool), (nxt, counts)

    (tokens, pos, steps, k_pool, v_pool), (toks, counts) = lax.scan(
        one, (tokens, pos, steps, k_pool, v_pool), None, length=n_steps)
    return toks, tokens, pos, steps, k_pool, v_pool, counts


def _prefill_chunk_kinds(params: Dict, k_pool, v_pool, chunks, btabs, starts,
                         n_valids, seeds, temps, topks, cfg: SwaMoeConfig,
                         pages: _Pages):
    """One prompt chunk for K prefilling slots in a single dispatch; the
    arguments and the causality-by-length are ``gpt_engine.
    _prefill_chunk_paged``'s. Pad rows and pad lanes write to the scratch
    pages and reach no expert. The table is always the whole block table:
    the kernel's grid is traced, so a context costs what it holds and the
    program is not specialised by it. Returns (first tokens [K], pools,
    histograms [n_moe_layers, held + 1])."""
    kk, c = chunks.shape
    bs = k_pool.shape[2]
    ring, table = btabs[:, :pages.ring], btabs[:, pages.ring:]
    n_ctx = table.shape[1]
    rows = jnp.arange(c, dtype=jnp.int32)
    positions = starts[:, None] + rows[None, :]                # [K, C]
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    in_prompt = rows[None, :] < n_valids[:, None]
    valid = in_prompt & (table[:, :1] > 0)
    blk = jnp.minimum(safe_pos // bs, n_ctx - 1)
    dest = (jnp.where(valid, jnp.take_along_axis(table, blk, axis=1), 0),
            jnp.where(valid, jnp.take_along_axis(
                ring, blk % pages.ring, axis=1), 0))
    # A row past its lane's prompt attends position 0 alone in a global
    # layer; in a window layer it attends what the lane's last row does, so
    # that it does not stretch its tile's window back to the first page.
    lengths = (jnp.where(in_prompt, positions + 1, 1),
               jnp.where(in_prompt, positions + 1,
                         (starts + n_valids)[:, None]))
    x = params["embed"]["tok"][chunks].reshape(kk * c, cfg.d_model)
    x, k_pool, v_pool, counts = _scan_layers_over_kinds(
        params, x, k_pool, v_pool, pages, btabs,
        tuple(d.reshape(kk * c) for d in dest),
        (safe_pos % bs).reshape(kk * c), safe_pos.reshape(kk * c),
        valid.reshape(kk * c), tuple(n.reshape(kk * c) for n in lengths),
        c, cfg)
    last = jnp.take_along_axis(
        x.reshape(kk, c, cfg.d_model),
        (n_valids - 1).astype(jnp.int32)[:, None, None], axis=1)[:, 0]
    firsts = _pick(_head(params, last, cfg), seeds, jnp.zeros_like(seeds),
                   temps, topks)
    return firsts, k_pool, v_pool, counts


class SwaMoePaged(PagedModel):
    """This family as the engine's scheduler sees it. It is told the bank
    (``max_slots``) and the prefill chunk, which size the slots' rings."""

    reads_pages_held = True         # ops/paged_attention.py
    shares_prefix = False           # module docstring
    prefill_by_context = False      # the kernel's grid is traced

    def __init__(self, cfg: SwaMoeConfig, max_slots: int, prefill_chunk: int):
        self.cfg = cfg
        self.max_slots = max_slots
        self.chunk_rows = max(1, min(int(prefill_chunk), cfg.max_len))

    def ring_pages(self, block_size: int) -> int:
        """Pages in a slot's ring: a prefill chunk writes its rows before
        any of them attends, so the ring holds the chunk and the window
        before its first row at once; one more for a chunk that starts
        inside a page."""
        return -(-(self.cfg.window + self.chunk_rows) // block_size) + 1

    def _page_bytes(self, block_size: int) -> int:
        """Keys and values of one page of one layer."""
        return (2 * block_size * self.cfg.kv_width
                * np.dtype(self.cfg.dtype).itemsize)

    def kind_bytes(self, block_size: int) -> Tuple[int, int]:
        page = self._page_bytes(block_size)
        return (self.cfg.layers_of(GLOBAL) * page,
                self.cfg.layers_of(WINDOW) * page)

    def block_bytes(self, block_size: int) -> int:
        return self.kind_bytes(block_size)[0]

    def pages_read(self, length: int, rows: int,
                   block_size: int) -> Tuple[int, int]:
        last = -(-length // block_size)
        first_key = max(length - rows + 1 - self.cfg.window, 0)
        return last, last - first_key // block_size

    def attends_straight(self, rows: int) -> bool:
        cfg = self.cfg
        return straight_line(rows, cfg.n_heads, cfg.n_kv_heads)

    def _pages(self, block_size: int, pool) -> _Pages:
        return _Pages(self.cfg, pool.shape[1], self.max_slots,
                      self.ring_pages(block_size))

    def pool_arrays(self, n_blocks: int, block_size: int):
        cfg = self.cfg
        pages = (cfg.layers_of(GLOBAL) * n_blocks + cfg.layers_of(WINDOW)
                 * (1 + self.max_slots * self.ring_pages(block_size)))
        shape = (1, pages, block_size, cfg.kv_width)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)

    def shard(self, mesh, params):
        raise NotImplementedError(
            "the window/global routed family is served on one device: it "
            "has no partition rules yet (fewer K/V heads than chips, "
            "expert placement and the exchange are ROADMAP B2/B9); pass "
            "mesh=None")

    # The jitted wrappers look the step functions up in this module when
    # traced, as the other families' do, and carry names of their own onto
    # the device trace: jit_swa_moe_decode_step,
    # jit_swa_moe_decode_fused_<n>, jit_swa_moe_prefill_chunk.

    def decode_step(self, block_size: int):
        def swa_moe_decode_step(params, k_pool, v_pool, btabs, tokens, pos,
                                seeds, steps, temps, topks):
            return _decode_step_kinds(
                params, k_pool, v_pool, btabs, tokens, pos, seeds, steps,
                temps, topks, cfg=self.cfg,
                pages=self._pages(block_size, k_pool))

        return swa_moe_decode_step

    def decode_fused(self, block_size: int, n_steps: int):
        def decode_fused(params, k_pool, v_pool, btabs, tokens, pos, seeds,
                         steps, temps, topks):
            return _decode_multi_step_kinds(
                params, k_pool, v_pool, btabs, tokens, pos, seeds, steps,
                temps, topks, cfg=self.cfg,
                pages=self._pages(block_size, k_pool), n_steps=n_steps)

        decode_fused.__name__ = f"swa_moe_decode_fused_{n_steps}"
        decode_fused.__qualname__ = decode_fused.__name__
        return decode_fused

    def prefill_chunk(self, block_size: int):
        def swa_moe_prefill_chunk(params, k_pool, v_pool, chunks, btabs,
                                  starts, n_valids, seeds, temps, topks):
            return _prefill_chunk_kinds(
                params, k_pool, v_pool, chunks, btabs, starts, n_valids,
                seeds, temps, topks, cfg=self.cfg,
                pages=self._pages(block_size, k_pool))

        return swa_moe_prefill_chunk

    def routing(self, extras) -> Optional[dict]:
        return routing_counters(extras[0], self.cfg)


class SwaMoeEngineModel(GptEngineModel):
    """The family served through the continuous-batching engine, under the
    GPT engine model's wire contract (INPUT_IDS [1, L], optional MAX_TOKENS,
    TEMPERATURE, TOP_K, SEED; one OUTPUT_IDS response a token)."""

    name = "swa_moe_engine"

    def __init__(self, cfg: Optional[SwaMoeConfig] = None, seed: int = 0,
                 params: Optional[Dict] = None, max_slots: int = 8,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 prefill_chunk: int = 32):
        Model.__init__(self)
        self.cfg = cfg or swa_moe_tiny()
        self.inputs, self.outputs = wire_tensors()
        if params is None:
            params = init_params(jax.random.PRNGKey(seed), self.cfg)
        self.engine = GenerationEngine(
            SwaMoePaged(self.cfg, max_slots, prefill_chunk), params,
            max_slots=max_slots, scope_name=self.name, block_size=block_size,
            n_blocks=n_blocks, prefill_chunk=prefill_chunk)
