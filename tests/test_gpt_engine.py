"""Paged KV cache engine tests: gather equivalence vs the contiguous
reference, prefix caching, block accounting under churn, and admission
gating on pool pages (plus the /metrics families the pool exposes)."""

import dataclasses
import os
import queue
import re
import threading
import time

import jax
import numpy as np
import pytest

from tritonclient_tpu import _kvcache
from tritonclient_tpu.models import gpt, gpt_engine
from tritonclient_tpu.models.gpt_engine import GenerationEngine

import sys

sys.path.insert(0, "scripts")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from check_metrics_exposition import check_exposition  # noqa: E402


def _collect(req):
    """Drain one request's out queue -> list of ints (raises on error)."""
    toks = []
    while True:
        t = req.out.get(timeout=120)
        if t is None:
            return toks
        if isinstance(t, BaseException):
            raise t
        toks.append(int(t[0]))


def _reference(params, prompt, max_new, cfg, **kw):
    return [int(np.asarray(t).flatten()[0])
            for t in gpt.generate_tokens(params, prompt, max_new, cfg, **kw)]


def _wait_idle(engine, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(r is None for r in engine._slot_req):
            return
        time.sleep(0.02)  # tpulint: disable=TPU001
    raise AssertionError(f"engine not idle: {engine._slot_req}")


def _engine_step_arguments(engine):
    """(the decode bank, a one-lane one-page chunk of 8): what the engine's
    three jitted wrappers take, for lowering or tracing them by hand."""
    import jax.numpy as jnp

    bank = (engine.params, engine._k, engine._v, engine._btabs,
            engine._tokens, engine._pos, engine._seeds, engine._steps,
            engine._temps, engine._topks)
    z = jnp.zeros((1,), jnp.int32)
    chunk = (engine.params, engine._k, engine._v,
             jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 1), jnp.int32),
             z, jnp.ones((1,), jnp.int32), z,
             jnp.zeros((1,), jnp.float32), z)
    return bank, chunk


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class _Served:
    """A model family behind the engine, and its plain reference: what the
    equality tests below need of either family, on one device or on a tp
    mesh of virtual ones."""

    def __init__(self, model, params, reference, mesh=None):
        self.model, self.params, self.mesh = model, params, mesh
        self.reference = reference      # (prompt, max_new, **sampling)
        self.vocab_size = getattr(model, "cfg", model).vocab_size

    def engine(self, **settings):
        return GenerationEngine(self.model, self.params, mesh=self.mesh,
                                **settings)

    def prompts(self, seed, lengths):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, self.vocab_size, (1, n)).astype(np.int32)
                for n in lengths]


def _served_gpt(cfg, params, tp=1):
    mesh = None
    if tp > 1:
        from tritonclient_tpu.parallel import build_mesh

        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} virtual devices")
        mesh = build_mesh({"tp": tp}, jax.devices()[:tp])
    return _Served(
        cfg, params,
        lambda prompt, n, **kw: _reference(params, prompt, n, cfg, **kw),
        mesh)


def _served_mla_moe():
    """The test-size MLA / routed-expert configuration; its reference is
    the benchmark's plain float32 forward pass, one token at a time, picked
    by the shared sampler on the shared (seed, step) keys. (The family
    refuses a mesh: tests/test_mla_moe.py.)"""
    from benchmarks import reference_mla_moe
    from test_mla_moe import shape_of
    from tritonclient_tpu.models import mla_moe

    cfg = mla_moe.mla_moe_tiny()
    params = mla_moe.init_params(jax.random.PRNGKey(3), cfg)
    shape = shape_of(cfg)

    # Causal, so a sequence padded to one length reads the same at the
    # positions it holds: one compile for every prompt and step.
    logits_of = jax.jit(
        lambda tokens: reference_mla_moe.logits(params, tokens, shape))

    def reference(prompt, n, temperature=0.0, top_k=0, seed=0):
        sequence = np.zeros((64,), np.int32)
        held = prompt.shape[1]
        sequence[:held] = prompt[0]
        for step in range(n):
            logits = logits_of(sequence)[held + step - 1]
            sequence[held + step] = int(gpt.sample_token(
                logits[None], gpt.sampling_key(seed, step), temperature,
                top_k)[0])
        return [int(t) for t in sequence[held:held + n]]

    return _Served(mla_moe.MlaMoePaged(cfg), params, reference)


@pytest.fixture(scope="module", params=[
    "gpt_tiny-tp1", "gpt_small-tp1", "gpt_small-tp2", "mla_moe_tiny-tp1"])
def served(request, tiny):
    """Both families through the one engine, the GPT family on a tp = 2
    virtual mesh too (float32, as ``chip_smoke.py`` compares tp = 4 with
    tp = 1): everything below the ``PagedModel`` seam differs, the slot
    state above it is the same code. ``gpt_small`` keeps its widths, heads
    and positions and is cut to two layers and a vocabulary of 4,096: the
    cases run beside five other xdist workers, some of which judge
    orderings on the clock."""
    import jax.numpy as jnp

    family, tp = request.param.split("-tp")
    if family == "gpt_tiny":
        return _served_gpt(*tiny)
    if family == "mla_moe_tiny":
        return _served_mla_moe()
    cfg = dataclasses.replace(gpt.gpt_small(), n_layers=2, vocab_size=4096,
                              dtype=jnp.float32)
    return _served_gpt(cfg, gpt.init_params(jax.random.PRNGKey(0), cfg),
                       int(tp))


# --------------------------------------------------------------------------- #
# gather equivalence: paged decode == contiguous reference, token-for-token   #
# --------------------------------------------------------------------------- #


def test_paged_decode_matches_reference_concurrent_mixed(served):
    """Concurrent requests with prompt lengths straddling block edges
    (15/16/17 around block_size=16) must each reproduce the contiguous
    single-request reference exactly: the pool gather reconstructs the
    dense cache geometry, so paging may not change a single token. Nor
    may the slot-state update: the five join in bursts and alone, and the
    fifth takes a slot (and its row) that another has just left."""
    engine = served.engine(max_slots=4, prefill_chunk=8)
    try:
        prompts = served.prompts(11, [5, 15, 16, 17, 33])
        max_news = [12, 9, 8, 7, 10]
        refs = [served.reference(p, n) for p, n in zip(prompts, max_news)]
        # Five requests over four slots: the fifth queues and joins when
        # a slot frees mid-flight.
        reqs = [engine.submit(p, n) for p, n in zip(prompts, max_news)]
        outs = [_collect(r) for r in reqs]
        assert outs == refs
    finally:
        engine.shutdown()


def test_paged_sampled_decode_matches_reference(served):
    """Sampled decoding rides the same shared (seed, step) key schedule
    as the single-request path — identical tokens, not just identical
    distributions. Two sampled requests share the bank with a greedy one
    over two slots, so seeds, temperatures and top-k join, free and join
    again through the slot-state update."""
    engine = served.engine(max_slots=2)
    try:
        prompts = served.prompts(3, [21, 9, 14])
        settings = [dict(temperature=0.8, top_k=12, seed=77), {},
                    dict(temperature=1.1, top_k=0, seed=2**31 + 5)]
        refs = [served.reference(p, 10, **kw)
                for p, kw in zip(prompts, settings)]
        reqs = [engine.submit(p, 10, **kw)
                for p, kw in zip(prompts, settings)]
        assert [_collect(r) for r in reqs] == refs
    finally:
        engine.shutdown()


def test_donating_slot_clock_advance_keeps_token_identity(tiny):
    """Regression for the donation-discipline fix (TPU015): the unfused
    decode branch advances pos/steps through a jit donating both
    operands, and the loop rebinds the results over the donated names.
    Running a full generation with the tpusan donation poisoner wrapped
    around that jit must report zero read-after-donate findings — and
    the token stream must still match the contiguous reference exactly
    (the CPU backend ignores donation, so any drift would be a logic
    bug, not a backend artifact)."""
    from tritonclient_tpu import sanitize
    from tritonclient_tpu.sanitize import _jax as sj

    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        engine._advance = sj.donating(
            engine._advance, donate_argnums=(0, 1),
            label="_advance_slot_clocks")
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, cfg.vocab_size, (1, 13)).astype(np.int32)
        ref = _reference(params, prompt, 12, cfg)
        sanitize.enable(mode="report")
        try:
            with sanitize.capture() as cap:
                got = _collect(engine.submit(prompt, 12))
                stale = [f for f in cap.findings if f.rule == "TPU015"]
        finally:
            sanitize.disable()
        assert stale == []
        assert got == ref
    finally:
        engine.shutdown()


# --------------------------------------------------------------------------- #
# prefix caching                                                              #
# --------------------------------------------------------------------------- #


def test_prefix_cache_hit_reproduces_tokens_and_counts_events(tiny):
    """Re-submitting a prompt must (a) hit its cached full blocks,
    (b) produce the exact same token stream through the shared pages,
    and (c) count hits once per committed admission."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, cfg.vocab_size, (1, 40)).astype(np.int32)
        ref = _reference(params, prompt, 8, cfg)
        first = _collect(engine.submit(prompt, 8))
        ev = engine._prefix.snapshot_events()
        # (40 - 1) // 16 = 2 matchable full blocks, all cold.
        assert ev["miss"] == 2 and ev["hit"] == 0
        again = _collect(engine.submit(prompt, 8))
        ev = engine._prefix.snapshot_events()
        assert ev["hit"] == 2 and ev["miss"] == 2
        assert first == ref and again == ref
        # A prompt sharing only the FIRST block hits exactly one block
        # (chain hashes: equal keys imply equal full prefixes).
        half = prompt.copy()
        half[0, 16:] = rng.integers(0, cfg.vocab_size, 24)
        ref_half = _reference(params, half, 6, cfg)
        assert _collect(engine.submit(half, 6)) == ref_half
        ev = engine._prefix.snapshot_events()
        assert ev["hit"] == 3 and ev["miss"] == 3
    finally:
        engine.shutdown()


def test_block_hash_chains_depth():
    """Equal block contents at different depths hash differently; equal
    full prefixes hash equal."""
    a = _kvcache.block_hash(0, [1, 2, 3, 4])
    b = _kvcache.block_hash(a, [1, 2, 3, 4])
    assert a == _kvcache.block_hash(0, [1, 2, 3, 4])
    assert a != b
    assert b == _kvcache.block_hash(_kvcache.block_hash(0, [1, 2, 3, 4]),
                                    [1, 2, 3, 4])
    assert _kvcache.block_hash(0, [1, 2, 3, 5]) != a


# --------------------------------------------------------------------------- #
# block accounting                                                            #
# --------------------------------------------------------------------------- #


def test_block_pool_double_free_raises():
    pool = _kvcache.BlockPool(4, 16)
    bid = pool.try_alloc()
    assert pool.unref(bid)
    pool.release(bid)
    with pytest.raises(RuntimeError, match="double-free"):
        pool.unref(bid)
    # release of a still-referenced block refuses too
    b2 = pool.try_alloc()
    with pytest.raises(RuntimeError, match="refcount"):
        pool.release(b2)


def test_seeded_churn_never_double_frees_and_reconciles(tiny):
    """Sixty requests over a deliberately tiny pool — repeated prompts
    (prefix registration + hits + LRU eviction under pressure), random
    lengths straddling block edges, and mid-flight cancels. Any
    double-free raises inside the engine (surfacing here as a request
    error); afterwards every page must be back in exactly one place."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=4, n_blocks=9,
                              prefill_chunk=8)
    try:
        rng = np.random.default_rng(42)
        base = [rng.integers(0, cfg.vocab_size, (1, l)).astype(np.int32)
                for l in (17, 20, 33, 18, 16, 19)]
        live = []
        for i in range(60):
            p = base[int(rng.integers(len(base)))]
            if rng.random() < 0.3:  # unique tail: force fresh pages
                p = p.copy()
                p[0, -1] = int(rng.integers(cfg.vocab_size))
            req = engine.submit(p, int(rng.integers(1, 8)))
            live.append((req, rng.random() < 0.2))
            while len(live) >= 4:
                r, cancel = live.pop(0)
                if cancel:
                    # Cancel after (at most) the first token.
                    try:
                        r.out.get(timeout=120)
                    except queue.Empty:
                        pass
                    r.cancelled = True
                    with engine._cv:
                        engine._cv.notify_all()
                else:
                    _collect(r)
        for r, _ in live:
            r.cancelled = True
            with engine._cv:
                engine._cv.notify_all()
        _wait_idle(engine)
        pool, prefix = engine._pool, engine._prefix
        # Quiescent reconciliation: scratch is the only referenced page;
        # everything else is free or parked (refcount 0) on the LRU.
        assert pool.used_count == 1
        assert pool.free_count + prefix.evictable_count == pool.n_blocks - 1
        assert engine._broken is None
    finally:
        engine.shutdown()


# --------------------------------------------------------------------------- #
# slot state: one jitted update a burst, and the reset before the reuse       #
# --------------------------------------------------------------------------- #


def _submit_together(engine, prompts, max_new):
    """Queue ``prompts`` so that ONE admission pass sees them all: wait
    until the engine's thread is parked on its condition, then submit
    while holding it (it is re-entrant), so the loop cannot look at the
    queue in between."""
    _wait_idle(engine)
    deadline = time.time() + 30
    while (engine._thread is not None and not engine._cv._waiters
           and time.time() < deadline):
        time.sleep(0.01)  # tpulint: disable=TPU001
    with engine._cv:
        return [engine.submit(p, max_new) for p in prompts]


@pytest.fixture(scope="module")
def warmed(tiny):
    """An eight-slot engine warmed as a benchmark adapter warms one: the
    slot-state update, the prefill family of a one-page context, then a
    request alone (a fused window of 4, one of 2, a step) and a full
    bank."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=8, prefill_chunk=8)
    engine.warm_admission()
    engine.warm_prefill(ctx_blocks=(1,))
    prompt = np.arange(12, dtype=np.int32).reshape(1, 12) % cfg.vocab_size
    _collect(engine.submit(prompt, 8))
    for r in _submit_together(engine, [prompt + i for i in range(8)], 6):
        _collect(r)
    _wait_idle(engine)
    yield engine, prompt
    engine.shutdown()


@pytest.mark.parametrize("burst", [1, 3, 8])
def test_a_burst_joins_and_frees_by_one_update_each_and_compiles_nothing(
        warmed, burst):
    """``burst`` prompts of one length finish their prefill in one chunk
    dispatch and, with one budget, their generation in one decode
    dispatch. After ``warm_admission()`` + ``warm_prefill()`` the window
    compiles NOTHING (the eager writes compiled a family of scatters and
    slices for every burst size), and the slot state is written by exactly
    two dispatches: one that joins ``burst`` slots, one that frees them."""
    from benchmarks.harness import CompileCount
    from tritonclient_tpu import _stepscope

    engine, prompt = warmed
    was = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        with CompileCount() as compiles:
            reqs = _submit_together(
                engine, [prompt + 3 * i for i in range(burst)], 6)
            outs = [_collect(r) for r in reqs]
            _wait_idle(engine)
        updates = _stepscope.dump()["slot_updates"]
    finally:
        _stepscope.configure(was)
        _stepscope.reset()
    assert all(len(o) == 6 for o in outs)
    assert compiles.requests == 0
    assert [(u["joined"], u["freed"]) for u in updates] == [
        (burst, 0), (0, burst)]
    assert all(u["model"] == "gpt_engine" and u["host_ns"] > 0
               and u["start_ns"] > 0 for u in updates)


def test_a_freed_slots_row_is_reset_before_its_pages_are_reused(
        tiny, monkeypatch):
    """A slot is freed and its page handed to the next request in the same
    loop pass, while decode dispatches of the old bank are still in
    flight (the long request keeps the pipeline full). Every dispatch is
    recorded in the order the engine's thread enqueues it: pages go back to
    the pool and the slot's row is re-pointed at the scratch page by an
    update BEFORE any model dispatch that follows, so no decode step can
    write the old occupant's K/V into the new one's pages; a join's update
    follows its last chunk with no decode dispatch between. And the tokens
    say the same: the request that reused the page gets what it gets
    served alone."""
    cfg, params = tiny
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "1")
    # Six pages beside the scratch page: the long request holds three, the
    # short one one, and the third needs three: it waits for the short
    # one's page, and for its slot.
    engine = GenerationEngine(cfg, params, max_slots=2, n_blocks=7,
                              prefill_chunk=8)
    events = []

    def spy(name, fn, what=lambda *a, **k: None):
        def spied(*args, **kwargs):
            events.append((name, what(*args, **kwargs)))
            return fn(*args, **kwargs)
        return spied

    engine._step = spy("decode", engine._step)
    engine._prefill_chunk_fn = spy("chunk", engine._prefill_chunk_fn)
    engine._update_slots = spy(
        "update", engine._update_slots,
        lambda *a: tuple(tuple(np.flatnonzero(a[8][:, column]))
                         for column in (gpt_engine._W_JOINED,
                                        gpt_engine._W_FREED)))
    engine._free_slot_blocks = spy("pages_back", engine._free_slot_blocks,
                                   lambda slot: slot)
    try:
        rng = np.random.default_rng(17)
        long_, short, third = (
            rng.integers(0, cfg.vocab_size, (1, n)).astype(np.int32)
            for n in (8, 4, 40))
        reqs = [engine.submit(long_, 40), engine.submit(short, 2),
                engine.submit(third, 8)]
        outs = [_collect(r) for r in reqs]
        _wait_idle(engine)
        # The short request's page went to the third in the pass that
        # freed it: it is admitted with no model dispatch in between.
        assert outs == [_reference(params, long_, 40, cfg),
                        _reference(params, short, 2, cfg),
                        _reference(params, third, 8, cfg)]
    finally:
        engine.shutdown()
    model = ("decode", "chunk")
    freed_slots = [e[1] for e in events if e[0] == "pages_back"]
    assert len(freed_slots) == 3
    for at, (name, what) in enumerate(events):
        if name == "pages_back":
            # The reset of this slot's row: the next update, with only
            # other slots' pages going back before it.
            after = events[at + 1:]
            upto = next(i for i, e in enumerate(after) if e[0] == "update")
            assert all(e[0] == "pages_back" for e in after[:upto]), after
            assert what in after[upto][1][1]
        if name == "update" and what[0]:
            # A join: straight after the chunk that finished the prompt.
            assert events[at - 1][0] == "chunk", events[at - 3:at + 1]
    # The third request's first chunk came after the short one's reset,
    # while the long one still had dispatches to come.
    reset = next(i for i, e in enumerate(events)
                 if e[0] == "update" and e[1][1])
    assert any(e[0] == "chunk" for e in events[reset:])
    assert any(e[0] == "decode" for e in events[reset:])
    assert sum(e[0] == "decode" for e in events[:reset]) >= 2


# --------------------------------------------------------------------------- #
# admission gates on pages                                                    #
# --------------------------------------------------------------------------- #


def test_admission_blocks_on_pool_exhaustion_and_resumes(tiny):
    """With pages for exactly one full-budget request, the second request
    parks (FIFO head) until the first finishes, then completes — and the
    block shows up in the engine's _pending state while it waits."""
    cfg, params = tiny
    # max_blocks = 64/16 = 4 per request; pool of 5 = scratch + one
    # request's worth.
    engine = GenerationEngine(cfg, params, max_slots=2, n_blocks=5)
    try:
        rng = np.random.default_rng(9)
        pa = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
        pb = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
        ra = engine.submit(pa, 50)  # ceil(58/16) = 4 pages: whole pool
        rb = engine.submit(pb, 50)
        # B cannot reserve while A holds the pool: it parks as _pending.
        deadline = time.time() + 30
        while time.time() < deadline and engine._pending is None:
            time.sleep(0.02)  # tpulint: disable=TPU001
        assert engine._pending is rb
        assert _collect(ra) == _reference(params, pa, 50, cfg)
        assert _collect(rb) == _reference(params, pb, 50, cfg)
    finally:
        engine.shutdown()


def test_warm_prefill_compiles_without_touching_pool(tiny):
    """warm_prefill drives every lane bucket through the chunk fn with
    all-scratch tables: the pool stays untouched (only the reserved
    scratch page is held), the idle-only guard matches warm_admission,
    and a real generation afterwards is unaffected."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=4, prefill_chunk=8)
    try:
        _wait_idle(engine)
        engine.warm_prefill(ctx_blocks=(1, 3))
        assert engine._pool.used_count == 1  # scratch only
        prompt = np.arange(10, dtype=np.int32).reshape(1, 10) % cfg.vocab_size
        warmed = _collect(engine.submit(prompt, 6))
        assert warmed == _reference(params, prompt, 6, cfg)
        # Busy engine refuses: the chunk fn donates the pools, so a warm
        # dispatch racing the engine loop would corrupt live state.
        hold = engine.submit(np.zeros((1, 8), np.int32), 30)
        first = hold.out.get(timeout=60)
        assert not isinstance(first, BaseException)
        with pytest.raises(RuntimeError, match="requires an idle engine"):
            engine.warm_prefill()
        hold.cancelled = True
    finally:
        engine.shutdown()


def test_request_larger_than_pool_fails_fast(tiny):
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=2, n_blocks=3)
    try:
        req = engine.submit(np.zeros((1, 8), np.int32), 50)  # needs 4 > 2
        with pytest.raises(RuntimeError, match="KV pages"):
            _collect(req)
        # The engine keeps serving poolable requests afterwards.
        small = engine.submit(np.zeros((1, 8), np.int32), 4)  # 1 page
        assert len(_collect(small)) == 4
    finally:
        engine.shutdown()


# --------------------------------------------------------------------------- #
# /metrics exposition                                                         #
# --------------------------------------------------------------------------- #


def test_metrics_expose_kv_and_prefix_families(tiny):
    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.server import InferenceServer

    cfg, _params = tiny
    model = GptEngineModel(cfg=cfg, max_slots=2, prefill_chunk=8)
    with InferenceServer(models=[model], http=False) as server:
        # Two identical 40-token prompts: the second admission hits.
        rng = np.random.default_rng(21)
        prompt = rng.integers(0, cfg.vocab_size, (1, 40)).astype(np.int32)
        for _ in range(2):
            _collect(model.engine.submit(prompt, 4))
        text = server.core.prometheus_metrics()
    assert check_exposition(text) == []
    assert 'nv_engine_kv_blocks_used{model="gpt_engine"}' in text
    assert 'nv_engine_kv_blocks_total{model="gpt_engine"}' in text
    for event in ("hit", "miss", "evict"):
        assert (f'nv_engine_prefix_cache_events_total{{model="gpt_engine"'
                f',event="{event}"}}') in text
    # The counted hits from the second admission made it to the wire.
    hit_line = [l for l in text.splitlines()
                if 'prefix_cache_events_total{model="gpt_engine",event="hit"'
                in l][0]
    assert int(hit_line.rsplit(" ", 1)[1]) >= 2


class TestKvExpositionViolations:
    HEAD = (
        "# HELP nv_engine_kv_blocks_used x\n"
        "# TYPE nv_engine_kv_blocks_used gauge\n"
        "# HELP nv_engine_kv_blocks_total x\n"
        "# TYPE nv_engine_kv_blocks_total gauge\n"
        "# HELP nv_engine_prefix_cache_events_total x\n"
        "# TYPE nv_engine_prefix_cache_events_total counter\n"
    )

    def _good_rows(self):
        rows = [
            'nv_engine_kv_blocks_used{model="gpt_engine"} 3',
            'nv_engine_kv_blocks_total{model="gpt_engine"} 9',
        ]
        rows += [
            f'nv_engine_prefix_cache_events_total{{model="gpt_engine"'
            f',event="{e}"}} 0'
            for e in ("hit", "miss", "evict")
        ]
        return rows

    def test_good_document_passes(self):
        assert check_exposition(
            self.HEAD + "\n".join(self._good_rows()) + "\n"
        ) == []

    def test_noncanonical_event(self):
        rows = self._good_rows()
        rows[2] = ('nv_engine_prefix_cache_events_total'
                   '{model="gpt_engine",event="vibes"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("vibes" in e for e in errors)

    def test_missing_event_row(self):
        rows = [r for r in self._good_rows() if 'event="evict"' not in r]
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("missing event rows" in e for e in errors)

    def test_used_exceeds_total(self):
        rows = self._good_rows()
        rows[0] = 'nv_engine_kv_blocks_used{model="gpt_engine"} 12'
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("nv_engine_kv_blocks_total" in e for e in errors)

    def test_gauge_label_set(self):
        rows = self._good_rows()
        rows.append('nv_engine_kv_blocks_used{model="m",version="1"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("label set" in e for e in errors)

    def test_negative_gauge(self):
        rows = self._good_rows()
        rows[0] = 'nv_engine_kv_blocks_used{model="gpt_engine"} -1'
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("< 0" in e for e in errors)


class TestInflightExpositionViolations:
    """The in-flight gauge's exposition contract (PR 13), checked the same
    way as the paged-KV families: synthetic documents through the real
    checker, one mutation per violation class."""

    HEAD = (
        "# HELP nv_engine_inflight_steps x\n"
        "# TYPE nv_engine_inflight_steps gauge\n"
    )

    def _good_rows(self):
        return ['nv_engine_inflight_steps{model="gpt_engine"} 2']

    def test_good_document_passes(self):
        assert check_exposition(
            self.HEAD + "\n".join(self._good_rows()) + "\n"
        ) == []

    def test_inflight_label_set(self):
        rows = self._good_rows()
        rows.append('nv_engine_inflight_steps{model="m",version="1"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("label set" in e for e in errors)

    def test_negative_inflight(self):
        rows = self._good_rows()
        rows[-1] = 'nv_engine_inflight_steps{model="gpt_engine"} -1'
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("in-flight depth" in e for e in errors)

    def test_live_snapshot_counts_dispatches_in_flight(self):
        """inflight_snapshot() feeds /metrics: a dispatch submitted and
        not yet delivered is one step in flight, and the depth never goes
        under zero."""
        from tritonclient_tpu import _stepscope

        prev = _stepscope._mode
        _stepscope.configure("counters")
        _stepscope._aggregator.reset()
        try:
            _stepscope.inflight_update("m", 1)
            _stepscope.inflight_update("m", 1)
            _stepscope.inflight_update("m", -1)
            assert _stepscope.inflight_snapshot() == [("m", 1)]
            _stepscope.inflight_update("m", -2)
            assert _stepscope.inflight_snapshot() == [("m", 0)]
        finally:
            _stepscope._aggregator.reset()
            _stepscope.configure(prev)


class TestCompileExpositionViolations:
    """The compile-plane exposition contract (PR 20): distinct-lowering
    gauge + retrace counter per jitted callable, one mutation per
    violation class through the real checker."""

    HEAD = (
        "# HELP nv_engine_compile_cache_entries x\n"
        "# TYPE nv_engine_compile_cache_entries gauge\n"
        "# HELP nv_engine_retrace_total x\n"
        "# TYPE nv_engine_retrace_total counter\n"
    )

    def _good_rows(self):
        return [
            'nv_engine_compile_cache_entries'
            '{model="gpt_engine",callable="decode_step"} 1',
            'nv_engine_compile_cache_entries'
            '{model="gpt_engine",callable="prefill_chunk"} 3',
            'nv_engine_retrace_total'
            '{model="gpt_engine",callable="decode_step"} 0',
            'nv_engine_retrace_total'
            '{model="gpt_engine",callable="prefill_chunk"} 2',
        ]

    def test_good_document_passes(self):
        assert check_exposition(
            self.HEAD + "\n".join(self._good_rows()) + "\n"
        ) == []

    def test_entries_label_set(self):
        rows = self._good_rows()
        rows.append('nv_engine_compile_cache_entries{model="m"} 1')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("label set" in e for e in errors)

    def test_retrace_label_set(self):
        rows = self._good_rows()
        rows.append(
            'nv_engine_retrace_total'
            '{model="m",callable="f",version="1"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("label set" in e for e in errors)

    def test_rendered_series_with_zero_entries(self):
        rows = self._good_rows()
        rows[0] = ('nv_engine_compile_cache_entries'
                   '{model="gpt_engine",callable="decode_step"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("at least one entry" in e for e in errors)

    def test_retraces_exceed_entries_minus_one(self):
        """Every retrace is an entry beyond the first, so per series
        retraces > entries - 1 means the two streams desynced."""
        rows = self._good_rows()
        rows[2] = ('nv_engine_retrace_total'
                   '{model="gpt_engine",callable="decode_step"} 1')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("nv_engine_retrace_total" in e and "- 1" in e
                   for e in errors)

    def test_live_snapshot_counts_distinct_keys_only(self):
        """note_compile() feeds /metrics: re-dispatching a seen
        signature is free, each new one past the first is a retrace."""
        from tritonclient_tpu import _stepscope

        prev = _stepscope._mode
        _stepscope.configure("counters")
        _stepscope._aggregator.reset()
        try:
            for key in ("4x1x64", "4x2x64", "4x1x64", "4x4x64"):
                _stepscope.note_compile("m", "prefill_chunk", key)
            _stepscope.note_compile("m", "decode_step", "bank:2x8:fuse:1")
            rows = _stepscope.compile_snapshot()
            assert ("m", "prefill_chunk", 3, 2) in rows
            assert ("m", "decode_step", 1, 0) in rows
        finally:
            _stepscope._aggregator.reset()
            _stepscope.configure(prev)


# --------------------------------------------------------------------------- #
# tpusan lanes ride the existing markers: these tests use only the engine's  #
# public surface, so both sanitizer lanes pick them up via tests/ discovery. #
# --------------------------------------------------------------------------- #


def test_named_locks_registered():
    """The pool/prefix locks go through sanitize.named_lock so the tpusan
    lock-order witness can see them."""
    pool = _kvcache.BlockPool(4, 16)
    cache = _kvcache.PrefixCache(pool)
    # When the sanitizer is inactive these are plain locks; the contract
    # here is just that both structures route through the helper and
    # remain usable.
    bid = pool.try_alloc()
    cache.register(_kvcache.block_hash(0, [1]), bid)
    cache.release_block(bid)
    assert cache.evictable_count == 1
    assert cache.evict_lru() is not None


# --------------------------------------------------------------------------- #
# names on the device trace, and the request timeline through the server      #
# --------------------------------------------------------------------------- #


def test_engine_executables_are_jitted_under_their_own_names(tiny):
    """The HLO modules (and with them the profile's `XLA Modules` line) read
    jit_decode_step / jit_decode_fused_<n> / jit_prefill_chunk, where bare
    functools.partials gave jit(<unknown>) for all three."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        bank, chunk = _engine_step_arguments(engine)
        lowered = {
            "jit_decode_step": engine._step.lower(*bank),
            "jit_decode_fused_4": engine._multi_step_fn(4).lower(*bank),
            "jit_decode_fused_2": engine._multi_step_fn(2).lower(*bank),
            "jit_prefill_chunk": engine._prefill_chunk_fn.lower(*chunk),
            "jit__advance_slot_clocks": engine._advance.lower(
                engine._pos, engine._steps),
        }
        for name, low in lowered.items():
            assert f"module @{name} " in low.as_text()[:200], name
    finally:
        engine.shutdown()


def test_the_named_wrappers_find_the_step_functions_when_traced(
        tiny, monkeypatch):
    """A step function replaced by module attribute (the benchmark's fault
    tests do) is what a new engine traces, fused path included."""
    from tritonclient_tpu.models import gpt_engine

    cfg, params = tiny
    produce = gpt_engine._decode_step_paged
    calls = []

    def spy(*args, **kwargs):
        calls.append(1)
        return produce(*args, **kwargs)

    monkeypatch.setattr(gpt_engine, "_decode_step_paged", spy)
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        prompt = np.arange(1, 10, dtype=np.int32).reshape(1, 9)
        assert _collect(engine.submit(prompt, 8)) == _reference(
            params, prompt, 8, cfg)
    finally:
        engine.shutdown()
    assert calls     # traced through the module attribute, not a captured one


def test_request_timeline_carries_the_servers_receipt_stamps(tiny):
    """Through the gRPC front end the core hands the request's TraceContext
    timeline down beside the cancel event: the engine's record of the
    request starts at the wire, recv <= core <= submit. With stepscope off
    nothing is stamped and nothing is kept."""
    from tritonclient_tpu import _stepscope
    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.server import InferenceServer
    import tritonclient_tpu.grpc as grpcclient

    cfg, _params = tiny
    model = GptEngineModel(cfg=cfg, max_slots=2, prefill_chunk=8)
    prev = _stepscope.mode()

    def generate(server, n_tokens):
        done: "queue.Queue" = queue.Queue()
        client = grpcclient.InferenceServerClient(server.grpc_address)
        client.start_stream(callback=lambda result, error: done.put(
            (result, error)))
        try:
            # Another prompt per call: no prefix-cache hit shortens it.
            prompt = np.arange(n_tokens, n_tokens + 19,
                               dtype=np.int32).reshape(1, 19)
            inputs = [grpcclient.InferInput("INPUT_IDS", [1, 19], "INT32"),
                      grpcclient.InferInput("MAX_TOKENS", [1], "INT32")]
            inputs[0].set_data_from_numpy(prompt)
            inputs[1].set_data_from_numpy(np.array([n_tokens], np.int32))
            sent_ns = time.monotonic_ns()
            client.async_stream_infer(model.name, inputs,
                                      enable_empty_final_response=True)
            tokens = 0
            while True:
                result, error = done.get(timeout=120)
                assert error is None, error
                out = result.as_numpy("OUTPUT_IDS")
                tokens += int(out is not None and out.size > 0)
                final = result.get_response().parameters.get(
                    "triton_final_response")
                if final is not None and final.bool_param:
                    return sent_ns, tokens
        finally:
            client.stop_stream()
            client.close()

    try:
        with InferenceServer(models=[model], http=False) as server:
            _stepscope.configure(_stepscope.MODE_OFF)
            _stepscope.reset()
            assert generate(server, 3)[1] == 3
            doc = _stepscope.dump()
            assert doc["records"] == [] and doc["requests"] == []
            _stepscope.configure(_stepscope.MODE_COUNTERS)
            sent_ns, tokens = generate(server, 5)
            assert tokens == 5
            deadline = time.time() + 30
            while time.time() < deadline and not _stepscope.dump()["requests"]:
                time.sleep(0.02)  # tpulint: disable=TPU001
            (record,) = _stepscope.dump()["requests"]
    finally:
        _stepscope.configure(prev)
        _stepscope.reset()
    assert record["key"][1:] == [19, 5] and record["chunks"] == 3
    assert sent_ns <= record["recv_ns"] <= record["core_ns"] \
        <= record["submit_ns"] <= record["admitted_ns"]
    assert len(record["out_ns"]) == 5
    assert record["outcome"] == "finished"


# --------------------------------------------------------------------------- #
# the KV pool never travels through the layer scan                            #
# --------------------------------------------------------------------------- #


def _scan_eqns(jaxpr):
    """Every ``scan`` equation of a jaxpr, those of nested jaxprs (the jit
    wrapper, the fused scan's body, the sampler's cond) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scan_eqns(sub)


@pytest.mark.parametrize("program", ["decode", "fused_2", "prefill_chunk"])
def test_layer_scan_carries_the_pools_and_scans_neither(tiny, program):
    """The pools are carried values of the layer scan; no scan of the
    program has a scanned input or a stacked output with the pool's shape
    or one layer's. Scanned, every dispatch slices each layer's pages out
    and writes a second pool back (ROADMAP A10)."""
    cfg, params = tiny
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        bank, chunk = _engine_step_arguments(engine)
        jaxpr = {
            "decode": lambda: jax.make_jaxpr(engine._step)(*bank),
            "fused_2": lambda: jax.make_jaxpr(engine._multi_step_fn(2))(*bank),
            "prefill_chunk": lambda: jax.make_jaxpr(
                engine._prefill_chunk_fn)(*chunk),
        }[program]().jaxpr
        pool = tuple(engine._k.shape)
        wqkv = tuple(engine.params["layers"]["wqkv"].shape)
    finally:
        engine.shutdown()
    assert pool == (cfg.n_layers, 1 + 2 * (cfg.max_len // 16), 16,
                    cfg.n_heads * cfg.head_dim)
    pool_shaped = (pool, pool[1:], (1,) + pool[1:])
    layer_scans = []
    for eqn in _scan_eqns(jaxpr):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts:n_consts + n_carry]]
        scanned = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts + n_carry:]]
        stacked = [tuple(v.aval.shape) for v in eqn.outvars[n_carry:]]
        assert not [s for s in scanned + stacked if s in pool_shaped], (
            program, scanned, stacked)
        if wqkv in scanned:
            layer_scans.append(carried)
    assert len(layer_scans) == 1, layer_scans
    assert layer_scans[0].count(pool) == 2, layer_scans


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described (not attached) chips of a v5e host. Described
    inside the fixture, never at import: only one process may load the
    TPU's library."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_chip(v5e_devices):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_devices[0])


@pytest.fixture()
def compile_cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_COMPILE_SECONDS = 120.0   # a two-layer program compiles in a few seconds

_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _moves_of(hlo_text, dims, ops=_MOVES):
    """The instructions of a compiled module's text whose opcode is one of
    ``ops`` and whose result (a tuple's first part) has one of ``dims``
    (``"3,48,16,256"``)."""
    pattern = re.compile(r"\s*(?:ROOT )?\S+ = \(?\w+\[([\d,]*)\]\S* ("
                         + "|".join(ops) + r")\(")
    return [line.strip()[:160] for line in hlo_text.splitlines()
            if (m := pattern.match(line)) and m.group(1) in dims]


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("heads,head_dim", [(25, 64), (16, 128)])
def test_v5e_compiler_moves_no_pool_and_allocates_none(
        v5e_chip, compile_cache_off, monkeypatch, heads, head_dim, program):
    """Compiled for the v5e, a step holds no copy, dynamic-slice or
    dynamic-update-slice with the pool's or one layer's dimensions, and
    its temporaries stay under one layer's pool: the scatter is in place
    on the donated buffer and the attention is the Mosaic kernel over the
    whole pools. A 5-D pool fails the 25 x 64 case: the chip's default
    layout puts the page axis minor there and every layer's pages are
    re-laid out. At both head shapes the program holds the kernel's custom
    call, no float32 array as large as the table's view (slots x table
    positions x H x Dh elements) and no gather of the table's pages:
    nothing of the table's width is read outside the kernel."""
    import functools
    import re

    # The kernel compiles for the chip the program is compiled for; this
    # process's own backend is the CPU, which would pick the interpreter.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    import jax.numpy as jnp

    from tritonclient_tpu.models import gpt_engine

    slots, block_size, n_blocks, max_len, chunk = 2, 16, 48, 32, 4
    cfg = gpt.GptConfig(vocab_size=256, d_model=heads * head_dim,
                        n_layers=2, n_heads=heads,
                        d_ff=4 * heads * head_dim, max_len=max_len,
                        dtype=jnp.bfloat16)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))
    k_pool, v_pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: gpt_engine._block_pool_arrays(cfg, n_blocks, block_size)))
    i32, f32 = jnp.int32, jnp.float32
    if program == "decode":
        fn = gpt_engine._decode_step_paged
        args = (vec(i32, slots, max_len // block_size), vec(i32, slots),
                vec(i32, slots), vec(i32, slots), vec(i32, slots),
                vec(f32, slots), vec(i32, slots))
    else:
        fn = gpt_engine._prefill_chunk_paged
        args = (vec(i32, slots, chunk), vec(i32, slots, 2), vec(i32, slots),
                vec(i32, slots), vec(i32, slots), vec(f32, slots),
                vec(i32, slots))
    began = time.monotonic()
    compiled = jax.jit(
        functools.partial(fn, cfg=cfg, block_size=block_size),
        donate_argnums=(1, 2),
    ).lower(params, k_pool, v_pool, *args).compile()
    assert time.monotonic() - began < _COMPILE_SECONDS

    pool = tuple(k_pool.shape)
    pool_dims = {",".join(map(str, d))
                 for d in (pool, pool[1:], (1,) + pool[1:])}
    table_elements = args[0].shape[0] * (
        args[1 if program == "prefill_chunk" else 0].shape[1]
        * block_size) * heads * head_dim
    moved, wide, gathers = [], [], []
    text = compiled.as_text()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = \(?(\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        if (op in ("copy", "copy-start", "dynamic-slice",
                   "dynamic-update-slice") and dims in pool_dims):
            moved.append(line.strip()[:160])
        if not dims or np.prod(
                [int(d) for d in dims.split(",")]) < table_elements:
            continue
        if dtype == "f32":
            wide.append(line.strip()[:160])
        if op == "gather" or "gather" in line.split("calls=")[-1]:
            gathers.append(line.strip()[:160])
    assert not moved, moved
    # The widest float32 arrays left are the feed-forward's and the logits'.
    assert not [w for w in wide if "custom-call" not in w
                and str(4 * heads * head_dim) not in w
                and str(cfg.vocab_size) not in w], wide
    assert not gathers, gathers
    assert "tpu_custom_call" in text
    layer_pool_bytes = (n_blocks * block_size * heads * head_dim
                        * np.dtype(k_pool.dtype).itemsize)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool_bytes
    # The decode step's kernel is the straight-line body (one row a table,
    # every head's row in one product: 25 x 64 is a 1,600-wide contraction,
    # twelve and a half lane tiles), which Mosaic has just taken.
    from tritonclient_tpu.ops.paged_attention import straight_line

    if program == "decode":
        assert straight_line(1, heads, heads)


@pytest.mark.parametrize("window", [None, 128], ids=["global", "window"])
def test_v5e_compiler_takes_the_grouped_decode_kernel(
        v5e_chip, compile_cache_off, monkeypatch, window):
    """K-EXAONE's decode call, 64 query heads on 8 K/V heads of 128 over a
    16,384-position table, global and under the window of 128, compiles for
    the v5e on the body the rule gives it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    import jax.numpy as jnp

    from tritonclient_tpu.ops.paged_attention import paged_attention

    def on_chip(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    slots, n_ctx = 8, 1024
    pool = on_chip(jnp.bfloat16, 1, 2 * n_ctx + 1, 16, 8 * 128)
    compiled = jax.jit(
        lambda q, k, v, btabs, lengths: paged_attention(
            q, k, v, 0, btabs, lengths, window=window)
    ).lower(on_chip(jnp.bfloat16, slots, 64, 128), pool, pool,
            on_chip(jnp.int32, slots, n_ctx), on_chip(jnp.int32, slots)
            ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_v5e_compiler_moves_no_latent_pool_and_slices_no_expert_bank(
        v5e_chip, compile_cache_off, monkeypatch, program):
    """The MLA / routed-expert family's steps (models/mla_moe.py), compiled
    for the v5e at the published latent width (512 + 64, a page row padded
    to 640 lanes: at 576 the chip lays the pool page-minor and every step
    copies it in and out): no copy, dynamic-slice or dynamic-update-slice
    with the pool's dimensions, and none with one layer's expert bank's
    (the grouped product takes the stacked banks and the layer's index; a
    scanned bank is sliced out, 2.4 GB a layer at the published sizes).
    Kept in this file for its fixture: one process describes the chip."""
    import jax.numpy as jnp

    from tritonclient_tpu.models import mla_moe

    # The routed product is a Pallas kernel (ops/grouped_experts.py) that
    # picks the interpreter from the backend: compile the chip's form.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Every published width of JoyAI-LLM-Flash (the config's defaults), one
    # dense and two expert layers, a small vocabulary: at toy widths the
    # compiler unrolls the layers and stages whole banks in fast memory,
    # which says nothing about 256 experts of 2048 x 768.
    slots, block_size, n_blocks, chunk = 2, 16, 48, 32
    cfg = mla_moe.MlaMoeConfig(vocab_size=1024, n_layers=3, max_len=64)
    model = mla_moe.MlaMoePaged(cfg)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    (pool,) = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.pool_arrays(n_blocks, block_size)))
    assert pool.shape == (3, n_blocks, block_size, 640)
    i32, f32 = jnp.int32, jnp.float32
    if program == "decode":
        fn = model.decode_step(block_size)
        args = (vec(i32, slots, cfg.max_len // block_size), vec(i32, slots),
                vec(i32, slots), vec(i32, slots), vec(i32, slots),
                vec(f32, slots), vec(i32, slots))
    else:
        fn = model.prefill_chunk(block_size)
        args = (vec(i32, slots, chunk), vec(i32, slots, 2), vec(i32, slots),
                vec(i32, slots), vec(i32, slots), vec(f32, slots),
                vec(i32, slots))
    began = time.monotonic()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    assert time.monotonic() - began < _COMPILE_SECONDS

    shape = tuple(pool.shape)
    bank = (cfg.n_experts, cfg.d_model, cfg.d_expert)
    watched = {",".join(map(str, d)) for d in (
        shape, shape[1:], (1,) + shape[1:], bank, (bank[0], bank[2], bank[1]),
        (1,) + bank)}
    moved = _moves_of(compiled.as_text(), watched)
    assert not moved, moved
    # ... nor under another name: the step's temporaries stay under one
    # expert layer's bank (0.8 GB here).
    bank_bytes = 2 * cfg.n_experts * cfg.d_model * cfg.d_expert
    assert compiled.memory_analysis().temp_size_in_bytes < bank_bytes


# --------------------------------------------------------------------------- #
# tensor parallelism: what the partitioned program holds                      #
# --------------------------------------------------------------------------- #

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute", "collective-broadcast")


def _collectives_by_computation(hlo_text):
    """``{computation: [collective opcode, ...]}`` of a compiled module's
    text (an asynchronous pair counts once, at its ``-start``)."""
    found, name = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            name = line.split("(")[0].replace("ENTRY", "").strip(" %")
            continue
        m = re.search(r"[\])}] ([a-z][\w-]*)\(", line.partition(" = ")[2])
        if m and m.group(1).replace("-start", "") in _COLLECTIVES:
            found.setdefault(name, []).append(m.group(1).replace("-start", ""))
    return found


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_tp2_program_holds_the_two_all_reduces_a_layer_the_record_counts(
        v5e_devices, compile_cache_off, monkeypatch, program):
    """The collective count on a tp dispatch record comes from a formula
    (``expected_tp_collectives``: two a layer, GSPMD's, no python call site
    to count at). Here it is read off the program. Twice, because each
    compiler shows a part:

    On the tp=2 VIRTUAL mesh, the engine's own jitted step: the lowered
    module has one manual region (the paged-attention kernel's shard_map)
    and the partitioned one holds two all-reduces, both in the body of one
    loop of ``n_layers`` trips; times the trips that is the ``psum`` count
    the engine charges. The pool cannot be judged there: the kernel runs
    interpreted and its interpreter copies what it is given.

    Compiled for two DESCRIBED v5e chips, where the kernel is the Mosaic
    call: the same collectives in one loop body, and no all-gather, copy
    or slice with the dimensions of the pool, of a shard of it or of one
    layer's: a shard attends its own heads and the pages stay where they
    lie.

    What the formula never counted, and both compilers hold: two
    collective-permutes a layer. ``wqkv`` is column-sharded as one
    ``[d, 3d]`` matrix, so a shard's columns are not its heads' q, k and
    v, and ``jnp.split`` re-lays them across the shards (op_name
    ``.../split``). The record's ``psum`` is the all-reduces' count, not
    every collective's (ROADMAP A6).
    """
    import jax.numpy as jnp

    from tritonclient_tpu.models import gpt_engine
    from tritonclient_tpu.parallel import (build_mesh, named_sharding,
                                           tree_shardings)

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    n_layers, block_size = 3, 16      # three trips: an unrolled scan shows
    cfg = gpt.GptConfig(vocab_size=256, d_model=512, n_layers=n_layers,
                        n_heads=4, d_ff=2048, max_len=64,
                        dtype=jnp.bfloat16)

    def arguments(vec):
        i32, rest = jnp.int32, (vec(jnp.float32, 2), vec(jnp.int32, 2))
        if program == "prefill_chunk":      # two lanes of 8 rows, 2 pages
            return (vec(i32, 2, 8), vec(i32, 2, 2)) + (vec(i32, 2),) * 3 + rest
        return ((vec(i32, 2, cfg.max_len // block_size),)
                + (vec(i32, 2),) * 4 + rest)

    def all_reduces_of_one_loop(text):
        found = _collectives_by_computation(text)
        (body,) = found
        assert sorted(found[body]) == [
            "all-reduce", "all-reduce",
            "collective-permute", "collective-permute"], found
        (loop,) = [ln for ln in text.splitlines()
                   if re.search(rf"body=%{re.escape(body)}[,\s]", ln)]
        return loop

    # -- the virtual mesh: the engine's own executable ---------------------- #
    mesh = build_mesh({"tp": 2}, jax.devices()[:2])
    engine = GenerationEngine(
        cfg, gpt.init_params(jax.random.PRNGKey(0), cfg), max_slots=2,
        prefill_chunk=8, mesh=mesh)
    try:
        fn = (engine._step if program == "decode"
              else engine._prefill_chunk_fn)
        lowered = fn.lower(engine.params, *engine._pools, *arguments(
            lambda dtype, *shape: jnp.zeros(shape, dtype)))
        assert lowered.as_text().count("sdy.manual_computation") == 1
        loop = all_reduces_of_one_loop(lowered.compile().as_text())
        trips = int(re.search(r'"known_trip_count":{"n":"(\d+)"', loop)[1])
        assert trips == n_layers
        assert engine._expected_collectives == {"psum": 2 * trips}
    finally:
        engine.shutdown()

    # -- two described chips: the pool stays where it lies ------------------ #
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh({"tp": 2}, v5e_devices[:2])
    model = gpt_engine.GptPaged(cfg)
    model._mesh = mesh      # ``shard`` would place arrays; these are shapes
    everywhere = named_sharding(mesh)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=everywhere)

    shapes = jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=sharding),
        shapes, tree_shardings(mesh, shapes, gpt.PARTITION_RULES))
    # 1,024 pages: 25 MB a shard, too large to be staged in fast memory
    # whole (a toy pool is, and then copied in and out).
    pools = [jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=named_sharding(mesh, None, None, None,
                                                  "tp"))
        for a in jax.eval_shape(lambda: model.pool_arrays(1024, block_size))]
    fn = (model.decode_step if program == "decode"
          else model.prefill_chunk)(block_size)
    began = time.monotonic()
    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, *pools, *arguments(vec)).compile().as_text()
    assert time.monotonic() - began < _COMPILE_SECONDS
    all_reduces_of_one_loop(text)
    assert "tpu_custom_call" in text
    whole = tuple(pools[0].shape)
    shard = whole[:3] + (whole[3] // 2,)
    pool_dims = {",".join(map(str, d)) for full in (whole, shard)
                 for d in (full, full[1:], (1,) + full[1:])}
    moved = _moves_of(text, pool_dims, _MOVES + (
        "all-gather", "all-gather-start", "slice", "slice-start"))
    assert not moved, moved
