"""Checkable harness models over the four real scheduling cores.

Each builder returns a fresh :class:`~tritonclient_tpu.mc.Model` whose
threads drive the *real* code paths — ``_DynamicBatcher.submit``/
``_sweep_shed``/``_take_batch``/completion-wakeup, ``GenerationEngine``
admission/slot-free/cancel, ``BlockPool``/``PrefixCache`` alloc/free/
prefix-release, ``AdmissionController`` bucket/cap/pressure-shed — not
re-modeled logic. The driver threads replace only the surrounding
*infrastructure* the checker cannot control (the daemon dispatcher /
engine / delivery threads the cores spawn internally), re-issuing the
same calls those threads make, in the same order, against the same
state. Invariants assert the cross-schedule contracts: no response
lost, no slot or KV page leaked, shed counters reconcile, FIFO
preserved for no-deadline traffic.

These models are the safety net for the ROADMAP item-1 scheduler
extraction: they constrain observable behavior only through public
state, so they re-run unchanged against a unified scheduler.

Two ``demo_*`` fixtures (a lost wakeup and an AB-BA deadlock) carry
seeded bugs — they are the worked examples in README/tests and are
excluded from the default "run every harness" set.
"""

import threading
import types
from typing import Callable, Dict

from tritonclient_tpu import sanitize
from tritonclient_tpu.mc._explore import Explorer, ExploreResult, Model


class HarnessUnavailable(RuntimeError):
    """The harness's subject cannot be imported here (e.g. no jax)."""


class _AliveThread:
    """Quacks like a live ``threading.Thread``: pre-seeded into the
    engine/distributor thread slots so the real ``submit`` paths do not
    spawn uncontrolled daemon threads mid-run (the harness's controlled
    threads stand in for them)."""

    @staticmethod
    def is_alive() -> bool:
        return True

    @staticmethod
    def join(timeout=None):
        return None


# --------------------------------------------------------------------------- #
# batcher: submit / _sweep_shed / _take_batch / completion-wakeup             #
# --------------------------------------------------------------------------- #


def build_batcher() -> Model:
    from tritonclient_tpu.protocol._literals import SHED_REASON_CANCELLED
    from tritonclient_tpu.server._core import (
        CoreRequest,
        CoreTensor,
        _DynamicBatcher,
        _ModelStats,
    )

    m = Model("batcher")
    core = types.SimpleNamespace(
        _lock=sanitize.named_lock("InferenceCore._lock")
    )
    batcher = _DynamicBatcher(core)
    batcher._n_dispatchers = 0  # the model's dispatcher thread stands in
    model = types.SimpleNamespace(name="mc-batcher")
    stats = _ModelStats()

    def req(rid: str, cancelled: bool = False) -> CoreRequest:
        ev = threading.Event()
        if cancelled:
            ev.set()
        return CoreRequest(
            model_name="mc-batcher", id=rid,
            inputs=[CoreTensor(name="x", datatype="FP32", shape=[1, 4])],
            cancel_event=ev,
        )

    state = {
        "slots": [],        # (rid, slot) in per-thread submit order
        "completed": [],    # rids in completion order
        "swept": 0,
        "subs_done": 0,
    }

    def submitter_fifo():
        # Two same-signature submissions from ONE thread: their queue
        # order is their submit order, the FIFO contract under test.
        for rid in ("a1", "a2"):
            state["slots"].append((rid, batcher.submit(model, req(rid),
                                                       stats, cap=8)))
        state["subs_done"] += 1

    def submitter_cancelled():
        # Cancelled before the dispatcher can take it: the sweep must
        # shed it and the shed counter must reconcile.
        state["slots"].append(("c1", batcher.submit(
            model, req("c1", cancelled=True), stats, cap=8
        )))
        state["subs_done"] += 1

    def dispatcher():
        # The take half of _DynamicBatcher._run, minus the model
        # execution: sweep + take under the cv, finalize/complete
        # outside it, completion bookkeeping + wakeup back under it.
        while True:
            with batcher._cv:
                shed = batcher._sweep_shed()
                batch = batcher._take_batch() if batcher._queue else None
                if batch:
                    batcher._dispatching += 1
            if shed:
                batcher._finalize_shed(shed)
                state["swept"] += len(shed)
            for slot in batch or ():
                slot.response = f"resp-{slot.request.id}"
                slot.done = True
                slot.event.set()
                state["completed"].append(slot.request.id)
            if batch:
                with batcher._cv:
                    batcher._dispatching -= 1
                    batcher._cv.notify_all()
            answered = len(state["completed"]) + state["swept"]
            if state["subs_done"] == 2 and answered >= len(state["slots"]):
                return
            if not batch and not shed:
                with batcher._cv:
                    batcher._cv.wait(timeout=0.01)

    m.thread("submit-fifo", submitter_fifo)
    m.thread("submit-cancel", submitter_cancelled)
    m.thread("dispatcher", dispatcher)

    def no_response_lost():
        for rid, slot in state["slots"]:
            assert slot.done, f"slot {rid} never answered"
            assert (slot.response is None) != (slot.error is None), \
                f"slot {rid} must carry exactly one of response/error"
        return True

    def fifo_preserved():
        order = [r for r in state["completed"] if r in ("a1", "a2")]
        assert order == sorted(order), \
            f"no-deadline FIFO violated: completion order {order}"
        return True

    def shed_reconciles():
        assert sum(stats.shed_counts.values()) == state["swept"], (
            f"shed counters {stats.shed_counts} != swept {state['swept']}"
        )
        assert stats.shed_counts[SHED_REASON_CANCELLED] == 1
        return True

    def queue_drained():
        assert not batcher._queue, "slots left in the batcher queue"
        assert batcher._deadline_queued == 0
        assert batcher._dispatching == 0
        return True

    m.invariant("no response lost", no_response_lost)
    m.invariant("no-deadline FIFO preserved", fifo_preserved)
    m.invariant("shed counters reconcile", shed_reconciles)
    m.invariant("queue drained", queue_drained)
    return m


# --------------------------------------------------------------------------- #
# gpt engine: admission / slot-free / cancel                                  #
# --------------------------------------------------------------------------- #


def build_gpt_engine() -> Model:
    try:
        import numpy as np

        from tritonclient_tpu.models.gpt import gpt_tiny
        from tritonclient_tpu.models.gpt_engine import GenerationEngine
    except Exception as e:  # noqa: BLE001 — jax/numpy absent or broken
        raise HarnessUnavailable(f"gpt engine unavailable: {e}") from e

    m = Model("gpt_engine")
    # One usable KV page (n_blocks=2 = scratch + 1) and two slots: the
    # second admission MUST take the pool-exhausted head-of-line path
    # (engine._pending) and retry when the first request's page frees.
    eng = GenerationEngine(gpt_tiny(max_len=8), params={}, max_slots=2,
                           block_size=4, n_blocks=2, prefill_chunk=4)
    eng._thread = _AliveThread()        # harness thread runs the loop
    eng._dist._thread = _AliveThread()  # harness thread delivers
    eng.shutdown = lambda: None         # atexit must not touch mc locks

    state = {"reqs": {}, "subs": 0, "cancel_drained": False}
    prompt = np.zeros((1, 3), np.int32)

    def submitter(name: str):
        def run():
            state["reqs"][name] = eng.submit(prompt, max_new=1)
            state["subs"] += 1
        return run

    def delivered(req) -> bool:
        return req.remaining == 0

    def engine_loop():
        # The scheduling spine of GenerationEngine._run_loop — cancel
        # sweep, free processing, admission — without the decode/prefill
        # dispatches (no compute runs under the checker).
        for _ in range(40):
            with eng._cv:
                done = (eng._admit.empty() and eng._dist.free_q.empty()
                        and eng._pending is None
                        and all(r is None for r in eng._slot_req)
                        and state["subs"] == 2)
                if done:
                    break
                # Actionable now? A queued admission, a returned slot,
                # or a head-of-line retry with pages available. Anything
                # else (decode in flight, pool exhausted) parks on the
                # cv until a submit/completion wakeup, as _run_loop does.
                work = (not eng._admit.empty()
                        or not eng._dist.free_q.empty()
                        or (eng._pending is not None
                            and eng._pool.free_count > 0))
                if not work:
                    # Longer than the distributor's wait: the checker
                    # fires the EARLIEST timeout when every thread is
                    # blocked, and a slot awaiting delivery is the
                    # distributor's progress to make, not ours.
                    eng._cv.wait(timeout=5.0)
                    continue
            eng._release_cancelled()
            eng._process_frees()
            eng._admit_requests()
            # _advance_prefills' terminal bookkeeping: prefill chunks
            # complete instantly under the checker (its compute
            # dispatches are the one part of the loop not modeled).
            for slot in list(eng._prefilling):
                del eng._prefilling[slot]
            with eng._cv:
                eng._cv.notify_all()  # loop-top wakeup, as _run_loop does
        # Deterministic epilogue on the same thread: a request cancelled
        # while queued must be drained through the abandoned path.
        req_c = eng.submit(prompt, max_new=1)
        req_c.cancelled = True
        eng._admit_requests()
        state["reqs"]["c"] = req_c
        state["cancel_drained"] = req_c.out.get_nowait() is None

    def distributor():
        # The completion tail of _Distributor._deliver: final token out,
        # terminator queued, slot handed back on free_q, engine woken.
        done = set()
        while len(done) < 2:
            progressed = False
            for slot, req in enumerate(list(eng._slot_req)):
                if req is None or id(req) in done:
                    continue
                if slot in eng._prefilling:
                    continue  # tokens only flow once the prefill is done
                req.remaining = 0
                req.out.put(None)
                eng._dist.free_q.put([(slot, req)])
                with eng._cv:
                    eng._cv.notify_all()
                done.add(id(req))
                progressed = True
            if not progressed:
                with eng._cv:
                    eng._cv.wait(timeout=2.0)

    m.thread("submit-a", submitter("a"))
    m.thread("submit-b", submitter("b"))
    m.thread("engine-loop", engine_loop)
    m.thread("distributor", distributor)

    def no_page_leaked():
        # Everything freed: only the scratch page stays referenced.
        assert eng._pool.used_count == 1, (
            f"KV pages leaked: used_count {eng._pool.used_count} != 1 "
            "(scratch)"
        )
        assert eng._pool.free_count == 1
        return True

    def no_slot_leaked():
        assert all(r is None for r in eng._slot_req), "slot left occupied"
        assert eng._pending is None
        assert eng._admit.empty()
        assert eng._dist.free_q.empty()
        assert not eng._prefilling
        return True

    def every_request_terminated():
        for name in ("a", "b"):
            req = state["reqs"][name]
            assert delivered(req), f"request {name} never delivered"
        assert state["cancel_drained"], \
            "cancelled request never drained through the abandoned path"
        return True

    m.invariant("no KV page leaked", no_page_leaked)
    m.invariant("no slot leaked", no_slot_leaked)
    m.invariant("every request terminated", every_request_terminated)
    return m


# --------------------------------------------------------------------------- #
# kvcache: BlockPool alloc/free + PrefixCache register/release/evict          #
# --------------------------------------------------------------------------- #


def build_kvcache() -> Model:
    from tritonclient_tpu._kvcache import BlockPool, PrefixCache

    m = Model("kvcache")
    n_blocks = 4
    pool = BlockPool(n_blocks, block_size=1)
    prefix = PrefixCache(pool)
    H1 = 0x1234

    def producer():
        # Prefill path: allocate, publish one block under its chain
        # hash, release both (registered -> evictable LRU, unregistered
        # -> free list).
        b1 = pool.try_alloc()
        b2 = pool.try_alloc()
        if b1 is not None:  # the consumer may have drained the pool
            prefix.register(H1, b1)
            prefix.release_block(b1)
        if b2 is not None:
            prefix.release_block(b2)

    def consumer():
        # Prefix-hit path racing the producer: a hit refs the shared
        # block; a miss drains the pool and reclaims through evict_lru.
        bid = prefix.match(H1)
        if bid is not None:
            prefix.release_block(bid)
        taken = []
        while True:
            got = pool.try_alloc()
            if got is None:
                break
            taken.append(got)
        evicted = prefix.evict_lru()
        if evicted is not None:
            taken.append(evicted)
        for got in taken:
            prefix.release_block(got)

    m.thread("producer", producer)
    m.thread("consumer", consumer)

    def conservation():
        # Every block in exactly one of: free list, evictable LRU,
        # refcount > 0.
        free = pool.free_count
        used = pool.used_count
        evictable = prefix.evictable_count
        assert free + used + evictable == n_blocks, (
            f"block conservation violated: free {free} + used {used} + "
            f"evictable {evictable} != {n_blocks}"
        )
        assert used == 0, f"pages leaked: {used} blocks still referenced"
        return True

    m.invariant("no page leaked (free/evictable/ref partition)",
                conservation)
    return m


# --------------------------------------------------------------------------- #
# fleet admission: token bucket / concurrency cap / pressure shed             #
# --------------------------------------------------------------------------- #


def build_fleet_admission() -> Model:
    from tritonclient_tpu.fleet._admission import (
        AdmissionController,
        TenantQuota,
    )
    from tritonclient_tpu.protocol._literals import QUOTA_REASON_PRESSURE

    m = Model("fleet_admission")
    # Frozen clock: the token bucket never refills mid-run, so every
    # schedule sees the same arithmetic.
    ctl = AdmissionController(
        {
            "t": TenantQuota(rate=1.0, burst=2.0, max_outstanding=1),
            "be": TenantQuota(rate=0.0, priority="low"),
        },
        clock=lambda: 100.0,
    )
    state = {"attempts": 0, "admitted": 0, "rejected": 0, "pressure": 0}

    def paid_client():
        # admit/release pair under the concurrency cap: racing the
        # other paid client, exactly one of the overlapping admits may
        # see the cap.
        for _ in range(2):
            state["attempts"] += 1
            reason = ctl.admit("t")
            if reason is None:
                state["admitted"] += 1
                ctl.release("t")
            else:
                state["rejected"] += 1

    def best_effort_client():
        # Pressure shed: low-priority traffic under fleet pressure is
        # always rejected; without pressure it rides the unlimited rate.
        state["attempts"] += 1
        reason = ctl.admit("be", under_pressure=True)
        assert reason == QUOTA_REASON_PRESSURE
        state["rejected"] += 1
        state["pressure"] += 1
        state["attempts"] += 1
        reason = ctl.admit("be")
        if reason is None:
            state["admitted"] += 1
            ctl.release("be")
        else:
            state["rejected"] += 1

    m.thread("tenant-t-0", paid_client)
    m.thread("tenant-t-1", paid_client)
    m.thread("tenant-be", best_effort_client)

    def counters_reconcile():
        counts = ctl.rejection_counts()
        total_rejected = sum(
            n for reasons in counts.values() for n in reasons.values()
        )
        assert state["admitted"] + state["rejected"] == state["attempts"]
        assert total_rejected == state["rejected"], (
            f"rejection counters {counts} != observed {state['rejected']}"
        )
        assert counts["be"][QUOTA_REASON_PRESSURE] == state["pressure"]
        return True

    def nothing_outstanding():
        status = ctl.status()
        assert status["outstanding"] == {}, (
            f"outstanding not reconciled: {status['outstanding']}"
        )
        return True

    m.invariant("admit/reject counters reconcile", counters_reconcile)
    m.invariant("no outstanding leaked", nothing_outstanding)
    return m


# --------------------------------------------------------------------------- #
# seeded-bug demos (worked examples; excluded from the default set)           #
# --------------------------------------------------------------------------- #


def build_demo_lost_wakeup() -> Model:
    """The classic missed-signal bug: the consumer checks the flag
    OUTSIDE the cv's lock, so the producer's set+notify can both land
    between the check and the wait — and the untimed wait then sleeps
    forever. tpumc reports TPU011 with the exact schedule; the static
    TPU011 rule flags the same shape as wait-outside-predicate-loop."""
    m = Model("demo-lost-wakeup")
    cv = sanitize.named_condition("demo.cv")
    box = {"ready": False}

    def producer():
        box["ready"] = True
        sanitize.note_field_access(box, "ready", write=True,
                                   label="demo.ready")
        with cv:
            cv.notify_all()

    def consumer():
        sanitize.note_field_access(box, "ready", write=False,
                                   label="demo.ready")
        if not box["ready"]:  # BUG: check not repeated under the lock
            with cv:
                cv.wait()

    m.thread("producer", producer)
    m.thread("consumer", consumer)
    return m


def build_demo_deadlock() -> Model:
    """AB-BA lock-order inversion: one preemption inside the first
    critical section reaches the cyclic-wait state."""
    m = Model("demo-deadlock")
    la = sanitize.named_lock("demo.lock_a")
    lb = sanitize.named_lock("demo.lock_b")

    def forward():
        with la:
            with lb:
                pass

    def backward():
        with lb:
            with la:
                pass

    m.thread("forward", forward)
    m.thread("backward", backward)
    return m


# --------------------------------------------------------------------------- #
# registry                                                                    #
# --------------------------------------------------------------------------- #

#: name -> builder. ``demo_*`` entries carry seeded bugs and are
#: excluded from :data:`DEFAULT_HARNESSES`.
HARNESSES: Dict[str, Callable[[], Model]] = {
    "batcher": build_batcher,
    "gpt_engine": build_gpt_engine,
    "kvcache": build_kvcache,
    "fleet_admission": build_fleet_admission,
    "demo_lost_wakeup": build_demo_lost_wakeup,
    "demo_deadlock": build_demo_deadlock,
}

DEFAULT_HARNESSES = ("batcher", "gpt_engine", "kvcache", "fleet_admission")

#: Per-harness exploration budgets (schedules): the gpt engine rebuilds
#: real device-state vectors per schedule, so its cap is tighter.
SCHEDULE_BUDGETS: Dict[str, int] = {
    "batcher": 1500,
    "gpt_engine": 400,
    "kvcache": 1500,
    "fleet_admission": 1500,
    "demo_lost_wakeup": 200,
    "demo_deadlock": 200,
}


def run_harness(name: str, preemption_budget: int = 2,
                max_schedules: int = 0, deadline_s: float = 60.0,
                seed: int = 0, prune: str = "dpor") -> ExploreResult:
    """Explore one registered harness under its default budgets."""
    if name not in HARNESSES:
        raise KeyError(
            f"unknown harness {name!r} (have: {', '.join(sorted(HARNESSES))})"
        )
    explorer = Explorer(
        HARNESSES[name], name=name, preemption_budget=preemption_budget,
        max_schedules=max_schedules or SCHEDULE_BUDGETS.get(name, 1000),
        deadline_s=deadline_s, seed=seed, prune=prune,
    )
    return explorer.explore()
