"""stepscope: the engine-step profiling plane — per-dispatch records
(dispatch time, what the work was, the delivery thread's stamps; a device
stage in ``sync`` mode only), the engine-loop states, one timeline per
request, collective counting, and its three sinks (/metrics summary
families, flight-recorder slowest-step stamps, Perfetto thread tracks)
plus ``step_report.py`` on top.

Deterministic: engines run greedy decoding on the virtual CPU mesh with
seeded params, and the synthetic-record tests use fixed timings.
"""

import gc
import importlib.util
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from tritonclient_tpu import _otel, _stepscope
from tritonclient_tpu.models import gpt

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _load_script(name: str, module: str):
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", name,
    )
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _stepscope_clean():
    """Every test starts and ends with stepscope off and empty, whatever
    the ambient TPU_STEPSCOPE was."""
    prev = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_OFF)
    _stepscope.reset()
    yield
    _stepscope.configure(prev)
    _stepscope.reset()


def _drain(engine, prompts, max_new):
    """Submit all prompts concurrently and collect each stream."""
    results = [None] * len(prompts)

    def consume(i):
        q = engine.submit(prompts[i], max_new).out
        toks = []
        while True:
            t = q.get(timeout=120)
            if t is None:
                break
            if isinstance(t, BaseException):
                raise t
            toks.append(int(t[0]))
        results[i] = toks

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


_PROMPTS_C4 = [
    np.array([[1, 5, 9, 2]], np.int32),
    np.array([[2, 4, 6]], np.int32),
    np.array([[9, 8, 7]], np.int32),
    np.array([[42]], np.int32),
]


# --------------------------------------------------------------------------- #
# mode plumbing                                                               #
# --------------------------------------------------------------------------- #


def test_env_mode_parsing(monkeypatch):
    for raw, want in [
        ("", _stepscope.MODE_OFF), ("0", _stepscope.MODE_OFF),
        ("off", _stepscope.MODE_OFF), ("false", _stepscope.MODE_OFF),
        ("no", _stepscope.MODE_OFF), ("1", _stepscope.MODE_COUNTERS),
        ("on", _stepscope.MODE_COUNTERS),
        ("sync", _stepscope.MODE_SYNC), ("SYNC", _stepscope.MODE_SYNC),
    ]:
        monkeypatch.setenv("TPU_STEPSCOPE", raw)
        assert _stepscope._env_mode() == want, raw


def test_off_mode_is_inert():
    assert not _stepscope.enabled()
    assert _stepscope.step_begin("m", _stepscope.PHASE_DECODE, 0) is None
    _stepscope.step_dispatched(None)  # must not raise
    _stepscope.step_end(None)
    _stepscope.note_collective("psum")  # no active step, scope off
    assert _stepscope.flight_attributes("m") == {}
    assert _stepscope.perfetto_events(0) == []
    step_rows, coll_rows = _stepscope.metrics_snapshot((0.5,))
    assert step_rows == [] and coll_rows == []


def test_expected_tp_collectives():
    assert _stepscope.expected_tp_collectives(2, 1) == {}
    assert _stepscope.expected_tp_collectives(2, 2) == {"psum": 4}
    assert _stepscope.expected_tp_collectives(8, 4) == {"psum": 16}


# --------------------------------------------------------------------------- #
# engine at c4: dispatch records, and no device stage in counters mode        #
# --------------------------------------------------------------------------- #


def _dispatches(records):
    return [r for r in records if r["phase"] in _stepscope.STEP_PHASES]


def test_engine_c4_counters_records_carry_no_device_stage():
    """Four concurrent generations through the engine in counters mode: no
    record pretends to a device time (the engine thread's post-dispatch
    remainder is bookkeeping, not the device), dispatch fits inside the
    step's span, decode and prefill both appear, and occupancy never
    exceeds the slot count."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=4)
    try:
        results = _drain(engine, _PROMPTS_C4, 6)
    finally:
        engine.shutdown()
    assert all(len(r) == 6 for r in results)

    doc = _stepscope.dump()
    assert doc["kind"] == "stepscope"
    assert all("device_us" not in r and "other_us" not in r
               for r in doc["records"])
    records = _dispatches(doc["records"])
    phases = {r["phase"] for r in records}
    assert _stepscope.PHASE_PREFILL_CHUNK in phases
    assert _stepscope.PHASE_DECODE in phases
    for r in records:
        assert 0 <= r["dispatch_us"] <= r["total_us"]
        assert 0 <= r["batch_size"] <= r["slots"] == 4
    stages = {stage for _, _, stage, *_ in
              _stepscope.metrics_snapshot((0.5,))[0]}
    assert stages == {_stepscope.STAGE_DISPATCH}
    decode = [r for r in records if r["phase"] == _stepscope.PHASE_DECODE]
    # Step indices are the engine loop's own sequence: strictly increasing.
    idx = [r["step_index"] for r in decode]
    assert idx == sorted(idx) and len(set(idx)) == len(idx)
    # tp=1 engine: no collectives charged.
    assert all(r["collectives"] == {} for r in decode)


def test_sync_mode_measures_device_stage():
    """sync mode brackets block_until_ready: the device stage is a real
    measurement and the three stages still partition the span."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(_stepscope.MODE_SYNC)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=16)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    try:
        _drain(engine, _PROMPTS_C4[:2], 4)
    finally:
        engine.shutdown()
    records = _dispatches(_stepscope.dump()["records"])
    assert records
    for r in records:
        assert r["dispatch_us"] >= 0
        assert r["device_us"] >= 0
        assert r["other_us"] >= 0
        assert r["dispatch_us"] + r["device_us"] + r["other_us"] \
            <= r["total_us"] + 2
    stages = {stage for _, _, stage, *_ in
              _stepscope.metrics_snapshot((0.5,))[0]}
    assert stages == set(_stepscope.STEP_STAGES)


def test_tp_engine_collectives_match_expected_per_step():
    """tp=2 engine: the forced all-reduces (one per row-sharded matmul —
    wo and w_out, so 2 per layer) are charged per dispatch via
    ``expected_tp_collectives``; every decode record must carry exactly
    that count times its fused micro-step count, and no time: a count is
    all the host knows of a GSPMD collective."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine
    from tritonclient_tpu.parallel import build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh({"dp": 1, "tp": 2}, jax.devices()[:2])
    engine = GenerationEngine(cfg, params, max_slots=2, mesh=mesh)
    try:
        _drain(engine, _PROMPTS_C4[:2], 4)
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    decode = [r for r in doc["records"]
              if r["phase"] == _stepscope.PHASE_DECODE]
    assert decode
    want = _stepscope.expected_tp_collectives(cfg.n_layers, 2)
    assert want == {"psum": 2 * cfg.n_layers}
    for r in decode:
        assert r["collectives"]["psum"]["count"] \
            == want["psum"] * r["micro_steps"]
        assert not [k for k in r if k.startswith("coll_")]
    # The aggregate counter matches micro-steps * per-step count.
    _, coll_rows = _stepscope.metrics_snapshot((0.5,))
    psum_total = sum(c for _, op, c in coll_rows if op == "psum")
    n_micro = sum(r["micro_steps"] for r in doc["records"]
                  if r["collectives"].get("psum"))
    assert psum_total == n_micro * want["psum"]


def test_note_collective_charges_active_step():
    """Explicit call-site notes (ppermute/all_to_all in parallel/) land on
    the thread's active step with byte accounting."""
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    rec = _stepscope.step_begin("m", _stepscope.PHASE_DECODE, 0)
    _stepscope.step_dispatched(rec)
    _stepscope.note_collective("ppermute", nbytes=1024)
    _stepscope.note_collective("ppermute", nbytes=1024)
    _stepscope.note_collective("all_to_all", nbytes=64)
    _stepscope.step_end(rec)
    d = rec.as_dict()
    assert d["collectives"]["ppermute"] == {"count": 2, "bytes": 2048}
    assert d["collectives"]["all_to_all"] == {"count": 1, "bytes": 64}


# --------------------------------------------------------------------------- #
# sinks: /metrics, flight recorder, Perfetto                                  #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("mode", [_stepscope.MODE_COUNTERS,
                                  _stepscope.MODE_SYNC])
def test_metrics_snapshot_and_exposition(mode):
    """The summary/counter families built from a live snapshot pass the
    exposition checker, including the stepscope label-set rules. A
    counters-mode server emits the dispatch stage alone; a ``sync`` one
    (the step waited on a real array) all three."""
    from tritonclient_tpu.server import InferenceServer

    _stepscope.configure(mode)
    _stepscope.reset()
    rec = _stepscope.step_begin("gpt", _stepscope.PHASE_DECODE, 0,
                                batch_size=2, slots=4)
    _stepscope.step_dispatched(rec)
    _stepscope.note_collective("psum", count=4)
    _stepscope.step_end(rec, outputs=jax.numpy.zeros((2,)))
    assert ("device_us" in _stepscope.dump()["records"][0]) \
        == (mode == _stepscope.MODE_SYNC)

    import urllib.request

    with InferenceServer() as server:
        text = urllib.request.urlopen(
            f"http://{server.http_address}/metrics", timeout=10
        ).read().decode()
    assert _stepscope.STEP_METRIC in text
    assert _stepscope.COLLECTIVES_METRIC in text
    assert 'stage="dispatch"' in text
    assert ('stage="device"' in text) == (mode == _stepscope.MODE_SYNC)
    assert ('stage="other"' in text) == (mode == _stepscope.MODE_SYNC)
    assert 'op="psum"' in text
    checker = _load_script("check_metrics_exposition.py", "cm_stepscope")
    assert checker.check_exposition(text) == []


def test_exposition_checker_catches_stepscope_violations():
    checker = _load_script("check_metrics_exposition.py", "cm_stepscope_v")
    fam = _stepscope.STEP_METRIC
    head = (f"# HELP {fam} step stage durations\n"
            f"# TYPE {fam} summary\n")
    # Wrong label set on a quantile row.
    bad = head + (f'{fam}{{model="m",stage="dispatch",quantile="0.5"}} 1\n'
                  f'{fam}_sum{{model="m",stage="dispatch",phase="decode"}} 1\n'
                  f'{fam}_count{{model="m",stage="dispatch",phase="decode"}} 1\n')
    assert any("label set" in e for e in checker.check_exposition(bad))
    # Non-canonical stage value.
    bad = head + (
        f'{fam}{{model="m",phase="decode",stage="gpu",quantile="0.5"}} 1\n'
        f'{fam}_sum{{model="m",phase="decode",stage="gpu"}} 1\n'
        f'{fam}_count{{model="m",phase="decode",stage="gpu"}} 1\n'
    )
    assert any("stage" in e for e in checker.check_exposition(bad))
    # Non-canonical phase value.
    bad = head + (
        f'{fam}{{model="m",phase="warmup",stage="device",quantile="0.5"}} 1\n'
        f'{fam}_sum{{model="m",phase="warmup",stage="device"}} 1\n'
        f'{fam}_count{{model="m",phase="warmup",stage="device"}} 1\n'
    )
    assert any("phase" in e for e in checker.check_exposition(bad))
    # Quantile rows must stay monotone (shared summary rule still applies).
    bad = head + (
        f'{fam}{{model="m",phase="decode",stage="device",quantile="0.5"}} 9\n'
        f'{fam}{{model="m",phase="decode",stage="device",quantile="0.99"}} 1\n'
        f'{fam}_sum{{model="m",phase="decode",stage="device"}} 10\n'
        f'{fam}_count{{model="m",phase="decode",stage="device"}} 2\n'
    )
    assert any("non-decreasing" in e for e in checker.check_exposition(bad))
    # Collectives counter: wrong label set.
    cfam = _stepscope.COLLECTIVES_METRIC
    bad = (f"# HELP {cfam} collectives\n# TYPE {cfam} counter\n"
           f'{cfam}{{model="m"}} 3\n')
    assert any("label set" in e for e in checker.check_exposition(bad))


def test_flight_attributes_stamp_slowest_step():
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    for i, pause in enumerate([0, 1, 0]):
        rec = _stepscope.step_begin("gpt", _stepscope.PHASE_DECODE, i,
                                    batch_size=3, slots=4)
        _stepscope.step_dispatched(rec)
        if pause:  # make step 1 the slowest deterministically
            import time
            time.sleep(0.02)  # tpulint: disable=TPU001 - sync test, no loop
        _stepscope.step_end(rec)
    attrs = _stepscope.flight_attributes("gpt")
    assert "step.slowest.device_us" not in attrs     # counters: no device clock
    assert attrs["step.slowest.index"] == 1
    assert attrs["step.slowest.phase"] == _stepscope.PHASE_DECODE
    assert attrs["step.slowest.batch_size"] == 3
    assert attrs["step.slowest.total_us"] >= 20_000
    assert _stepscope.flight_attributes("other-model") == {}


def test_perfetto_events_load_as_orphan_tracks():
    """The Perfetto sink's thread-scoped events survive the loader (minted
    track ids), reach trace_report without a parent-lookup crash, and
    step_report recovers the records from them."""
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    for i in range(3):
        rec = _stepscope.step_begin("gpt", _stepscope.PHASE_DECODE, i,
                                    batch_size=1, slots=2)
        _stepscope.step_dispatched(rec)
        _stepscope.step_end(rec)
    events = _stepscope.perfetto_events(epoch_ns=0)
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert "gpt/decode[0]" in names
    assert any(e.get("ph") == "M" for e in events)  # thread_name metadata
    doc = {"displayTimeUnit": "ns", "traceEvents": events}
    spans = _otel.load_spans(doc)
    assert len([s for s in spans if s["name"].startswith("gpt/")]) == 3
    assert all(s["trace_id"].startswith("track-") for s in spans)
    trace_report = _load_script("trace_report.py", "trace_report_scope")
    rendered = trace_report.report(spans, slowest=5, as_json=False)
    assert "gpt/decode[0]" in rendered
    step_report = _load_script("step_report.py", "step_report_perfetto")
    recs = step_report.load_records(doc)
    assert len(recs) == 3


# --------------------------------------------------------------------------- #
# step_report verdicts                                                        #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("mode", [_stepscope.MODE_COUNTERS,
                                  _stepscope.MODE_SYNC])
def test_step_report_verdict_from_engine_dump(mode):
    """End to end: drive the engine at c4, dump, and the report renders the
    engine's scope: a dominant-stage verdict from a ``sync`` dump, none
    (and the reason) from a counters one, which has no device clock; the
    loop states and the request table beside the step table either way."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(mode)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=4)
    try:
        _drain(engine, _PROMPTS_C4, 6)
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    step_report = _load_script("step_report.py", "step_report_e2e")
    analysis = step_report.analyze(
        step_report.load_records(doc),
        requests=step_report.load_requests(doc),
        deliveries=step_report.load_deliveries(doc),
        slot_updates=step_report.load_slot_updates(doc))
    model = analysis["models"]["gpt_engine"]
    rendered = step_report.render(analysis)
    # (The engine is shut down with the last tokens: a slot it frees on the
    # way down is reset by no dispatch.)
    assert model["slot_updates"]["joined"] == 4 >= model[
        "slot_updates"]["freed"]
    assert "  slot updates" in rendered
    if mode == _stepscope.MODE_SYNC:
        assert model["verdict"] in (
            step_report.VERDICT_DISPATCH, step_report.VERDICT_DEVICE,
            step_report.VERDICT_COLLECTIVE,
        )
    else:
        assert model["verdict"] == step_report.VERDICT_NO_DEVICE_CLOCK
        assert "device" not in model["mean_us"]
        assert "records no device time" in rendered
    assert "verdict:" in rendered and "decode" in rendered
    # Loop states are not steps: out of the phase table, in their own rows.
    assert not set(model["phases"]) & set(_stepscope.LOOP_STATES)
    assert model["n"] == len(_dispatches(doc["records"]))
    assert set(model["loop_states"]) <= set(_stepscope.LOOP_STATES)
    assert model["loop_states"] and "  loop " in rendered
    rows = model["requests"]
    assert len(rows) == 4 and all(r["tokens"] == 6 for r in rows)
    assert all(r["outcome"] == "finished" and r["wait_ms"] >= 0
               and r["worst_gap_ms"] >= 0 for r in rows)
    # The prefill span's four parts are its whole, request by request.
    for r in rows:
        parts = [r[k] for k in ("to_chunk_ms", "chunking_ms", "readback_ms",
                                "handover_ms")]
        assert min(parts) >= 0
        assert abs(sum(parts) - r["prefill_span_ms"]) < 0.01
        assert r["recv_ms"] is None and r["core_ms"] is None  # no server
    assert "worst_gap" in rendered and "  median" in rendered
    # What the work was, and the delivery thread's view of its result.
    decode = model["phases"]["decode"]
    assert decode["tokens_per_step"] > 0 and decode["ctx_tokens_per_step"] > 0
    assert set(model["deliveries"]) == {"decode", "prefill_chunk"}
    for cell in model["deliveries"].values():
        assert cell["n"] > 0
        assert all(cell[k]["p50"] >= 0 for k in
                   ("queue_wait_ms", "readback_ms", "handover_ms"))
    assert "  delivery decode" in rendered


def test_step_report_self_check_passes(capsys):
    step_report = _load_script("step_report.py", "step_report_sc")
    assert step_report.self_check() == 0
    assert "every loader" in capsys.readouterr().out


def test_step_report_cli_on_dump_file(tmp_path):
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    rec = _stepscope.step_begin("gpt", _stepscope.PHASE_DECODE, 0)
    _stepscope.step_dispatched(rec)
    _stepscope.step_end(rec)
    path = tmp_path / "scope.json"
    path.write_text(json.dumps(_stepscope.dump()))
    # A dump saved before PR 29 carries estimated collective times: read
    # like any other, the extra keys ignored.
    doc = _stepscope.dump()
    for r in doc["records"]:
        r.update({f"coll_{side}_us": us
                  for side, us in (("exposed", 120), ("hidden", 240))})
    doc["overlap"] = {"gpt|exposed": 120, "gpt|hidden": 240}
    older = tmp_path / "scope_pr28.json"
    older.write_text(json.dumps(doc))
    step_report = _load_script("step_report.py", "step_report_cli")
    for dump in (path, older):
        assert step_report.main([str(dump)]) == 0
        assert step_report.main([str(dump), "--json"]) == 0
        assert step_report.main([str(dump), "--compare", str(path)]) == 0


# --------------------------------------------------------------------------- #
# steps_completed on cancel                                                   #
# --------------------------------------------------------------------------- #


def test_cancel_event_carries_steps_completed():
    """The delivery thread mirrors the per-request token count onto the
    cancel_event, so shed/cancel finalization can stamp where in the
    decode loop the request died."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    ev = threading.Event()
    try:
        q = engine.submit(_PROMPTS_C4[0], 40, cancel_event=ev).out
        got = 0
        while got < 5:
            t = q.get(timeout=120)
            assert t is not None
            got += 1
        ev.set()
        while q.get(timeout=120) is not None:
            got += 1
    finally:
        engine.shutdown()
    steps = getattr(ev, "steps_completed", None)
    assert steps is not None and steps >= 5
    assert steps == got


# --------------------------------------------------------------------------- #
# one timeline per request, one per dispatch, and the loop states             #
# --------------------------------------------------------------------------- #

_CHUNK = 8
# A thread's CPU clock against the wall: the kernel advances the first in
# steps, and over a busy stretch of any length it ran up to 21 us ahead of
# the second on this sandbox.
_CLOCK_SLACK_US = 100


def _timeline_run(prompt_lens, max_new, mode=_stepscope.MODE_COUNTERS):
    """A traced run of a chunked-prefill engine; returns (results, dump)."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(mode)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=128)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=4, prefill_chunk=_CHUNK)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in prompt_lens]
    try:
        results = _drain(engine, prompts, max_new)
    finally:
        engine.shutdown()
    return prompts, results, _stepscope.dump()


def test_every_finished_request_leaves_one_ordered_timeline():
    """Six requests over four slots (two wait for a slot), prompts of two to
    five chunks: exactly one record each, every stamp in the order the work
    happened, one hand-over stamp per token, the chunk count of the prompt,
    and the key a reader outside the server can compute."""
    import zlib

    lens = [19, 23, 31, 37, 12, 40]
    prompts, results, doc = _timeline_run(lens, 9)
    assert all(len(r) == 9 for r in results)
    requests = doc["requests"]
    assert len(requests) == len(lens)
    by_key = {tuple(q["key"]): q for q in requests}
    assert set(by_key) == {
        (zlib.crc32(p.tobytes()), p.shape[1], 9) for p in prompts}
    for prompt in prompts:
        q = by_key[(zlib.crc32(prompt.tobytes()), prompt.shape[1], 9)]
        assert q["outcome"] == _stepscope.OUTCOME_FINISHED
        assert q["model"] == "gpt_engine"
        assert q["recv_ns"] is None and q["core_ns"] is None  # no server
        assert len(q["out_ns"]) == 9
        assert q["chunks"] == -(-prompt.shape[1] // _CHUNK)
        order = [q["submit_ns"], q["admitted_ns"], q["first_chunk_ns"],
                 q["last_chunk_ns"], q["first_ready_ns"], *q["out_ns"],
                 q["end_ns"]]
        assert order == sorted(order), q
        assert q["waited_for_pages"] is False
    # Two of six had to wait for a slot: their admission came after some
    # other request's last token.
    firsts_end = sorted(q["end_ns"] for q in requests)[0]
    assert sum(q["admitted_ns"] > firsts_end for q in requests) >= 2


def test_a_request_that_waited_for_pages_says_so():
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    # Pages for exactly one full-budget request: the second parks.
    engine = GenerationEngine(cfg, params, max_slots=2, n_blocks=5)
    try:
        results = _drain(engine, [_PROMPTS_C4[0], _PROMPTS_C4[1]], 50)
    finally:
        engine.shutdown()
    assert [len(r) for r in results] == [50, 50]
    waited = [q["waited_for_pages"] for q in _stepscope.dump()["requests"]]
    assert sorted(waited) == [False, True]


def test_a_cancelled_request_leaves_a_cancelled_timeline():
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    ev = threading.Event()
    try:
        q = engine.submit(_PROMPTS_C4[0], 40, cancel_event=ev).out
        got = 0
        while got < 3:
            assert q.get(timeout=120) is not None
            got += 1
        ev.set()
        while q.get(timeout=120) is not None:
            got += 1
    finally:
        engine.shutdown()
    (record,) = _stepscope.dump()["requests"]
    assert record["outcome"] == _stepscope.OUTCOME_CANCELLED
    assert len(record["out_ns"]) == got < 40
    assert record["end_ns"] >= record["out_ns"][-1]


def _delivery_of(doc):
    """(phase, step_index) -> the delivery record of that dispatch."""
    by_step = {(d["phase"], d["step_index"]): d for d in doc["deliveries"]}
    assert len(by_step) == len(doc["deliveries"])      # one per dispatch
    assert all(d["model"] == "gpt_engine" for d in doc["deliveries"])
    return by_step


def test_dispatch_records_say_what_the_work_was_and_when_it_reached_the_host():
    """Every decode record: tokens == batch x micro-steps over the whole
    bank's table, and one delivery record with the delivery thread's four
    stamps in order. Prefill chunks: the padded lane bucket and context
    bucket, the positions computed; only a chunk that finished a prompt
    has a delivery record."""
    lens = [19, 23, 31, 37]
    prompts, _, doc = _timeline_run(lens, 9)
    records = _dispatches(doc["records"])
    delivery_of = _delivery_of(doc)
    decode = [r for r in records if r["phase"] == _stepscope.PHASE_DECODE]
    assert decode
    for r in decode:
        d = delivery_of[(r["phase"], r["step_index"])]
        assert d["queued_ns"] <= d["taken_ns"] <= d["ready_ns"] \
            <= d["delivered_ns"]
        assert r["start_ns"] <= d["queued_ns"]
        assert r["tokens"] == r["batch_size"] * r["micro_steps"]
        assert r["lanes"] == r["slots"] == 4
        assert r["ctx_blocks"] == 128 // 16
        # every active slot holds at least its prompt and the first token
        assert r["ctx_tokens"] >= r["batch_size"] * (min(lens) + 1)
    assert sum(r["tokens"] for r in decode) >= len(lens) * (9 - 1)
    chunks = [r for r in records
              if r["phase"] == _stepscope.PHASE_PREFILL_CHUNK]
    assert sum(r["tokens"] for r in chunks) == sum(lens)
    for r in chunks:
        assert r["batch_size"] <= r["lanes"] <= 4
        assert r["lanes"] & (r["lanes"] - 1) == 0          # a power of two
        assert 0 < r["tokens"] <= r["batch_size"] * _CHUNK
        assert r["tokens"] <= r["ctx_tokens"] <= r["lanes"] * \
            r["ctx_blocks"] * 16
    with_delivery = [delivery_of[(r["phase"], r["step_index"])]
                     for r in chunks
                     if (r["phase"], r["step_index"]) in delivery_of]
    # 19 tokens in chunks of 8: the first two chunk dispatches finish no
    # prompt, so they have no delivery record and no readback was added to
    # observe them.
    assert len(with_delivery) < len(chunks)
    assert 1 <= len(with_delivery) <= len(lens)
    assert len(doc["deliveries"]) == len(decode) + len(with_delivery)
    for d in with_delivery:
        assert d["queued_ns"] <= d["taken_ns"] <= d["ready_ns"] \
            <= d["delivered_ns"]
    # A request's first token became ready on the item that finished it.
    ready = {d["ready_ns"] for d in with_delivery}
    assert {q["first_ready_ns"] for q in doc["requests"]} <= ready
    # Dispatch records enter the ring as they end, in the order dispatched.
    starts = [r["start_ns"] for r in records]
    assert starts == sorted(starts)


@pytest.mark.parametrize("lens", [[19], [19, 23, 31, 37]],
                         ids=["one_request", "four_requests"])
def test_dispatch_records_count_the_pages_under_the_lanes_lengths(lens):
    """``ctx_pages`` is what the paged-attention kernel visits: for a decode
    dispatch the sum over its live slots (and its micro-steps) of
    ceil((pos + 1) / block_size), pos + 1 being the context the slot holds;
    for a chunk dispatch the pages under each real lane's last position.
    With one request in flight the record's ``ctx_tokens`` is that one
    slot's, so the count is checked exactly; with four, between its bounds.
    It never passes the table's width, and ``kv_bytes`` is reckoned from it
    (the GPT family reads the pages held, not the table)."""
    from tritonclient_tpu.models.gpt_engine import GptPaged

    bs = 16
    _, _, doc = _timeline_run(lens, 40)
    records = _dispatches(doc["records"])
    decode = [r for r in records if r["phase"] == _stepscope.PHASE_DECODE]
    chunks = [r for r in records
              if r["phase"] == _stepscope.PHASE_PREFILL_CHUNK]
    assert decode and chunks
    block_bytes = GptPaged(gpt.gpt_tiny(max_len=128)).block_bytes(bs)
    for r in decode + chunks:
        steps = r["micro_steps"]
        assert 0 < r["ctx_pages"] <= steps * r["lanes"] * r["ctx_blocks"]
        assert r["kv_bytes"] == r["ctx_pages"] * block_bytes
    for r in decode:
        steps, live, held = r["micro_steps"], r["batch_size"], r["ctx_tokens"]
        if live == 1:
            assert r["ctx_pages"] == sum(
                -(-(held + i) // bs) for i in range(steps))
        # every slot's ceil is at least its share and under one page more
        grown = steps * held + live * steps * (steps - 1) // 2
        assert -(-grown // bs) <= r["ctx_pages"] < grown / bs + steps * live
    if len(lens) == 1:
        assert any(r["micro_steps"] > 1 for r in decode)   # fused counted too
        assert [r["ctx_pages"] for r in chunks] == [1, 1, 2]   # 8, 16, 19
    for r in chunks:
        assert -(-r["ctx_tokens"] // bs) <= r["ctx_pages"] \
            < r["ctx_tokens"] / bs + r["batch_size"]


@pytest.mark.parametrize("mode", [_stepscope.MODE_COUNTERS,
                                  _stepscope.MODE_OFF])
def test_dispatch_records_say_which_body_of_the_paged_kernel_ran(mode):
    """``attn_straight`` is a fact of the executable's shapes
    (``ops.paged_attention.straight_line``): true on every decode record
    (one query row a table, four heads), false on every chunk of 32 rows
    (128 stacked rows), and ``step_report.py`` gives each phase its share
    by dispatches and by pages read. With stepscope off there is no record
    and nothing is stamped."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(mode)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=128)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=4, prefill_chunk=32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in (40, 70)]
    try:
        _drain(engine, prompts, 5)
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    records = _dispatches(doc["records"])
    if mode == _stepscope.MODE_OFF:
        assert not records      # nothing to stamp
        return
    decode = [r for r in records if r["phase"] == _stepscope.PHASE_DECODE]
    chunks = [r for r in records
              if r["phase"] == _stepscope.PHASE_PREFILL_CHUNK]
    assert decode and len(chunks) >= 3      # lanes may share a dispatch
    assert all(r["attn_straight"] is True for r in decode)
    assert all(r["attn_straight"] is False for r in chunks)
    step_report = _load_script("step_report.py", "step_report_attn")
    analysis = step_report.analyze(step_report.load_records(doc))
    phases = analysis["models"]["gpt_engine"]["phases"]
    assert phases["decode"]["attention"] == {
        "n": len(decode), "dispatches": 1.0, "ctx_pages": 1.0}
    assert phases["prefill_chunk"]["attention"] == {
        "n": len(chunks), "dispatches": 0.0, "ctx_pages": 0.0}
    rendered = step_report.render(analysis)
    assert "attention decode" in rendered
    assert "attention prefill_chunk" in rendered


def test_loop_states_enter_the_ring_and_nothing_else():
    """ticket_wait / idle_wait / admit are records in the ring with the
    harness's fields, overlap neither a dispatch's bracket nor each other,
    and reach no sketch, no count and no /metrics row."""
    _, _, doc = _timeline_run([19, 23, 31, 37, 12, 40], 9)
    loops = [r for r in doc["records"]
             if r["phase"] in _stepscope.LOOP_STATES]
    assert {r["phase"] for r in loops} >= {_stepscope.LOOP_ADMIT,
                                           _stepscope.LOOP_JOIN}
    # one join per dispatch that finished a prompt, right behind it
    joins = [r for r in loops if r["phase"] == _stepscope.LOOP_JOIN]
    delivery_of = _delivery_of(doc)
    finishing = [r for r in _dispatches(doc["records"])
                 if r["phase"] == _stepscope.PHASE_PREFILL_CHUNK
                 and (r["phase"], r["step_index"]) in delivery_of]
    assert len(joins) == len(finishing)
    for join, chunk in zip(sorted(joins, key=lambda r: r["start_ns"]),
                           sorted(finishing, key=lambda r: r["start_ns"])):
        queued_ns = delivery_of[(chunk["phase"],
                                 chunk["step_index"])]["queued_ns"]
        assert chunk["start_ns"] + 1000 * chunk["total_us"] \
            <= join["start_ns"] + 1000
        assert join["start_ns"] <= queued_ns \
            <= join["start_ns"] + 1000 * (join["dispatch_us"] + 1)
    for r in loops:
        assert r["model"] == "gpt_engine" and r["slots"] == 4
        assert r["batch_size"] == 0 and r["micro_steps"] == 0
        assert r["dispatch_us"] >= 0 and r["start_ns"] > 0
        assert "device_us" not in r
    assert not set(_stepscope.STEP_PHASES) & set(_stepscope.LOOP_STATES)
    # What the harness labels a gap by: [start, start + dispatch_us).
    spans = sorted((r["start_ns"], r["start_ns"] + 1000 * r["dispatch_us"],
                    r["phase"]) for r in doc["records"]
                   if r["thread_name"] == "gpt-engine")
    for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
        assert end <= start, (a, b, end - start)
    step_rows, _ = _stepscope.metrics_snapshot((0.5,))
    assert {phase for _, phase, *_ in step_rows} <= set(
        _stepscope.STEP_PHASES)
    assert not [k for k in doc["step_counts"]
                if k.split("|")[1] in _stepscope.LOOP_STATES]
    assert _stepscope.flight_attributes("gpt_engine")[
        "step.slowest.phase"] in _stepscope.STEP_PHASES
    # The Perfetto sink shows them on the engine thread's track.
    names = {e["name"].split("[")[0] for e in _stepscope.perfetto_events(0)
             if e.get("ph") == "X"}
    assert "gpt_engine/admit" in names


def test_slot_updates_have_a_ring_of_their_own_and_add_up_to_the_requests():
    """One record a dispatch of the engine's slot-state update, with its
    fields; over a run the slots joined equal the requests that finished
    their prefill and the slots freed those that ended (six requests over
    four slots, one of them cancelled mid-stream); every update lies
    inside an ``admit`` (frees) or a ``join`` stretch of the loop, which
    is why it is no loop state itself; and the ring is not the dispatch
    ring (the harness's readers see nothing new)."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=128)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=4, prefill_chunk=_CHUNK)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in (19, 23, 31, 37, 12, 40)]
    try:
        reqs = [engine.submit(p, 9) for p in prompts[:-1]]
        gone = engine.submit(prompts[-1], 60)
        for r in reqs:
            while r.out.get(timeout=120) is not None:
                pass
        assert gone.out.get(timeout=120) is not None    # it joined
        gone.cancelled = True
        while gone.out.get(timeout=120) is not None:
            pass
        deadline = time.time() + 30
        while (any(r is not None for r in engine._slot_req)
               and time.time() < deadline):
            time.sleep(0.02)  # tpulint: disable=TPU001 - sync test, no loop
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    updates = doc["slot_updates"]
    assert updates and all(set(u) - {"runq_us"} == {
        "model", "joined", "freed", "start_ns", "host_ns", "cpu_us"}
        for u in updates)
    assert all(0 <= u["cpu_us"] <= u["host_ns"] // 1000 + _CLOCK_SLACK_US
               for u in updates)
    assert all(u["model"] == "gpt_engine" and u["host_ns"] > 0
               and u["joined"] + u["freed"] > 0
               and not (u["joined"] and u["freed"]) for u in updates)
    assert sum(u["joined"] for u in updates) == 6
    assert sum(u["freed"] for u in updates) == 6     # five ended, one left
    assert len(doc["requests"]) == 6
    assert not [r for r in doc["records"] if "joined" in r]
    stretches = {state: [(r["start_ns"],
                          r["start_ns"] + 1000 * (r["dispatch_us"] + 1))
                         for r in doc["records"] if r["phase"] == state]
                 for state in (_stepscope.LOOP_ADMIT, _stepscope.LOOP_JOIN)}
    for u in updates:
        state = (_stepscope.LOOP_JOIN if u["joined"]
                 else _stepscope.LOOP_ADMIT)
        assert any(lo <= u["start_ns"] and u["start_ns"] + u["host_ns"] <= hi
                   for lo, hi in stretches[state]), (u, state)
    # ... and scripts/step_report.py's row for them.
    step_report = _load_script("step_report.py", "step_report_slots")
    analysis = step_report.analyze(
        step_report.load_records(doc),
        slot_updates=step_report.load_slot_updates(doc))
    row = analysis["models"]["gpt_engine"]["slot_updates"]
    assert row["n"] == len(updates) and row["joined"] == row["freed"] == 6
    assert 0 < row["host_ms"]["p50"] <= row["host_ms"]["p95"]
    assert 0 <= row["off_cpu_ms"] <= row["host_total_ms"]
    assert "  slot updates" in step_report.render(analysis)
    assert "ms in all), off-cpu" in step_report.render(analysis)
    # A dump from before the ring existed has no row, and none is rendered.
    del doc["slot_updates"]
    older = step_report.analyze(
        step_report.load_records(doc),
        slot_updates=step_report.load_slot_updates(doc))
    assert older["models"]["gpt_engine"]["slot_updates"] is None
    assert "slot updates" not in step_report.render(older)


def test_idle_wait_is_recorded_when_the_engine_parks():
    """An engine with nothing to do waits on its condition: one idle_wait
    stretch from the last token to the next submit."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine
    import time

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    try:
        for _ in range(2):      # every executable compiled before the pause
            _drain(engine, _PROMPTS_C4[:1], 3)
        time.sleep(0.3)  # tpulint: disable=TPU001 - sync test, no loop
        _drain(engine, _PROMPTS_C4[:1], 3)
    finally:
        engine.shutdown()
    idle = [r for r in _stepscope.dump()["records"]
            if r["phase"] == _stepscope.LOOP_IDLE_WAIT]
    assert idle and max(r["dispatch_us"] for r in idle) >= 150_000


def test_stepscope_off_stamps_nothing():
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    try:
        req = engine.submit(_PROMPTS_C4[0], 4)
        assert req.span is None
        while req.out.get(timeout=120) is not None:
            pass
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    assert doc["records"] == [] and doc["requests"] == []
    assert doc["deliveries"] == [] and doc["slot_updates"] == []
    assert doc["step_counts"] == {}
    assert _stepscope.request_begin("m", _PROMPTS_C4[0], 4) is None
    _stepscope.request_end(None, _stepscope.OUTCOME_FINISHED)
    assert _stepscope.delivery_begin(None) is None
    _stepscope.delivery_end(None)
    _stepscope.step_abandon()
    assert _stepscope.clock() is None
    _stepscope.loop_state("m", _stepscope.LOOP_ADMIT, (1, 0, 0), (2, 0, 0))
    _stepscope.slot_update("m", 1, 0, (1, 0, 0), (2, 0, 0))
    _stepscope.loop_state("m", _stepscope.LOOP_ADMIT, None, None)
    _stepscope.slot_update("m", 1, 0, None, None)
    assert _stepscope.dump()["records"] == []
    assert _stepscope.dump()["slot_updates"] == []
    # ... and keeps nothing outside its own state: no hook on the
    # collector, no descriptor open, and a collection leaves no record
    gc.collect()
    assert _stepscope._gc_hook not in gc.callbacks
    assert _stepscope._sched_fds == {} and _stepscope.dump()["gc"] == []


def test_a_dispatch_that_raises_leaves_no_step_open(monkeypatch):
    """``step_abandon`` closes the open step's annotation and drops the
    record; the engine's failure handler calls it, so a decode dispatch
    that raises ends the request with an error and leaves no decode record
    and no annotation entered on the engine thread."""
    from tritonclient_tpu.models import gpt_engine

    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    rec = _stepscope.step_begin("m", _stepscope.PHASE_DECODE, 0)
    assert rec._annotation is not None and _stepscope._tls.active is rec
    _stepscope.step_abandon()
    assert rec._annotation is None and _stepscope._tls.active is None
    assert _stepscope.dump()["records"] == []

    closed = []
    close = _stepscope._close_annotation
    monkeypatch.setattr(_stepscope, "_close_annotation",
                        lambda r: (closed.append(r.phase), close(r)))

    def fail(*args, **kwargs):
        raise RuntimeError("dispatch failed")

    monkeypatch.setattr(gpt_engine, "_decode_step_paged", fail)
    monkeypatch.setattr(gpt_engine, "_decode_multi_step_paged", fail)
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = gpt_engine.GenerationEngine(cfg, params, max_slots=2)
    try:
        out = engine.submit(_PROMPTS_C4[0], 4).out
        items = [out.get(timeout=120)]          # the prefill's first token
        while not isinstance(items[-1], BaseException):
            assert items[-1] is not None
            items.append(out.get(timeout=120))
        assert "dispatch failed" in str(items[-1])
    finally:
        engine.shutdown()
    doc = _stepscope.dump()
    assert _stepscope.PHASE_DECODE not in {r["phase"] for r in doc["records"]}
    # the decode step's annotation was closed, by the failure handler
    assert closed[-1] == _stepscope.PHASE_DECODE
    (request,) = doc["requests"]
    assert request["outcome"] == _stepscope.OUTCOME_ERROR


def test_request_ring_takes_its_length_from_the_step_ring(monkeypatch):
    monkeypatch.setenv("TPU_STEPSCOPE_RING", "3")
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    for i in range(5):
        rec = _stepscope.request_begin("m", np.array([[i]], np.int32), 1)
        _stepscope.request_end(rec, _stepscope.OUTCOME_FINISHED)
        _stepscope.request_end(rec, _stepscope.OUTCOME_ERROR)  # once only
    requests = _stepscope.dump()["requests"]
    assert len(requests) == 3
    assert {q["outcome"] for q in requests} == {_stepscope.OUTCOME_FINISHED}


# --------------------------------------------------------------------------- #
# the host's share of a token: the handler's stamps, the threads' clocks,     #
# the collector                                                               #
# --------------------------------------------------------------------------- #


def _engine_model():
    from tritonclient_tpu.models.gpt_engine import GptEngineModel

    return GptEngineModel(gpt.gpt_tiny(max_len=64), max_slots=2)


def test_the_stream_handler_stamps_every_token_twice():
    """Through the generator every engine model shares
    (``GptEngineModel.infer``): one ``taken_ns`` and one ``resumed_ns`` a
    token, after the delivery thread's ``out_ns`` and in order. The
    delivery thread ends the request (and hands its record to the ring)
    before the handler has taken the last tokens: the ring holds the record
    and ``dump()`` the copy, so the last stamps are there. A stream that is
    closed early has stamped what it got."""
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    model = _engine_model()
    try:
        tokens = []
        for response in model.infer({
                "INPUT_IDS": _PROMPTS_C4[0],
                "MAX_TOKENS": np.array([7], np.int32)}):
            tokens.append(response["OUTPUT_IDS"])
            time.sleep(0.01)  # tpulint: disable=TPU001 - the handler lags the delivery thread on purpose
        assert len(tokens) == 7
        (q,) = _stepscope.dump()["requests"]
        assert q["outcome"] == _stepscope.OUTCOME_FINISHED
        assert len(q["taken_ns"]) == len(q["resumed_ns"]) == len(
            q["out_ns"]) == 7
        for out, taken, resumed in zip(q["out_ns"], q["taken_ns"],
                                       q["resumed_ns"]):
            assert out <= taken <= resumed
        assert all(resumed <= taken for resumed, taken
                   in zip(q["resumed_ns"], q["taken_ns"][1:]))
        # a record that is in the ring already (its request has ended)
        # still takes the handler's stamps: the ring holds the record
        late = _stepscope.request_begin("m", _PROMPTS_C4[3], 1)
        _stepscope.request_end(late, _stepscope.OUTCOME_FINISHED)
        late.taken_ns.append(5)
        late.resumed_ns.append(6)
        assert _stepscope.dump()["requests"][-1]["taken_ns"] == [5]
        assert _stepscope.dump()["requests"][-1]["resumed_ns"] == [6]
        # the copy is a copy
        q["taken_ns"].append(0)
        assert len(_stepscope.dump()["requests"][0]["taken_ns"]) == 7

        _stepscope.reset()
        stream = model.infer({"INPUT_IDS": _PROMPTS_C4[1],
                              "MAX_TOKENS": np.array([30], np.int32)})
        for _ in range(3):
            next(stream)
        stream.close()
        deadline = time.time() + 30
        while (not _stepscope.dump()["requests"]
               and time.time() < deadline):
            time.sleep(0.02)  # tpulint: disable=TPU001 - sync test, no loop
        (q,) = _stepscope.dump()["requests"]
        assert q["outcome"] == _stepscope.OUTCOME_CANCELLED
        assert len(q["taken_ns"]) == 3 and len(q["resumed_ns"]) == 2
        assert len(q["out_ns"]) >= 3
    finally:
        model.engine.shutdown()
    # its columns in scripts/step_report.py's request table
    step_report = _load_script("step_report.py", "step_report_egress")
    (row,) = step_report._request_rows([q])
    assert row["wake_ms"] >= 0 and row["handler_ms"] >= 0
    older = dict(q)
    del older["taken_ns"], older["resumed_ns"]
    (row,) = step_report._request_rows([older])
    assert row["wake_ms"] is None and row["handler_ms"] is None


def test_stepscope_off_stamps_no_token():
    model = _engine_model()
    try:
        assert len(list(model.infer({
            "INPUT_IDS": _PROMPTS_C4[0],
            "MAX_TOKENS": np.array([4], np.int32)}))) == 4
    finally:
        model.engine.shutdown()
    assert _stepscope.dump()["requests"] == []


def test_a_stretch_that_waits_for_a_lock_is_off_the_cpu_and_a_busy_one_is_on():
    """``wall - cpu`` of a stretch spent waiting for a lock another thread
    holds is about the wait, and the thread's CPU time next to nothing; a
    stretch spent computing is on the CPU for its length, but for what the
    thread stood on a run queue (this suite's workers share their cores:
    no wall-clock number is judged without it)."""
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    lock = threading.Lock()
    held = threading.Event()

    def hold():
        with lock:
            held.set()
            time.sleep(0.25)  # tpulint: disable=TPU001 - the other thread's hold

    holder = threading.Thread(target=hold)
    holder.start()
    held.wait(10)
    began = _stepscope.clock()
    with lock:
        ended = _stepscope.clock()
    holder.join()
    _stepscope.loop_state("m", _stepscope.LOOP_ADMIT, began, ended, 2)
    began = _stepscope.clock()
    spun = time.thread_time()
    while time.thread_time() - spun < 0.1:
        pass
    _stepscope.loop_state("m", _stepscope.LOOP_JOIN, began,
                          _stepscope.clock(), 2)
    waited, busy = _stepscope.dump()["records"]
    assert waited["phase"] == "admit" and busy["phase"] == "join"
    assert waited["dispatch_us"] - waited["cpu_us"] >= 150_000
    assert waited["cpu_us"] <= 50_000
    assert busy["cpu_us"] >= 99_000
    asleep = (busy["dispatch_us"] - busy["cpu_us"]
              - busy.get("runq_us", busy["dispatch_us"]))
    assert asleep <= 20_000
    for r in (waited, busy):
        assert r["cpu_us"] <= r["dispatch_us"] + _CLOCK_SLACK_US
        assert r.get("runq_us", 0) >= 0
    # ... and scripts/step_report.py's totals and shares on the loop's rows
    step_report = _load_script("step_report.py", "step_report_offcpu")
    waited["phase"], busy["phase"] = "admit", "join"
    states = step_report._loop_states([waited, busy])
    # (the busy stretch's share is what the other workers took of its core:
    # held to the waiting stretch's, not to a number)
    assert states["admit"]["off_cpu_share"] > 0.5
    assert states["join"]["off_cpu_share"] < states["admit"]["off_cpu_share"]
    rendered = step_report.render({"models": {"m": {
        "n": 0, "mean_us": {}, "collectives_per_step": 0, "verdict": "-",
        "phases": {}, "loop_states": states}}})
    assert "loop admit" in rendered and "off-cpu" in rendered


def test_every_record_of_a_run_carries_its_threads_cpu_time():
    """Dispatch records, loop states, slot updates and deliveries of an
    engine run: ``cpu_us`` on each, never longer than the stretch it is of
    (to the clocks' resolution), and the run-queue delay beside it where
    the thread's ``schedstat`` can be read."""
    _, _, doc = _timeline_run([19, 23, 31, 37, 12, 40], 9)
    readable = os.path.exists(f"/proc/self/task/{threading.get_native_id()}"
                              "/schedstat")
    walls = (
        [(r, r["dispatch_us"]) for r in doc["records"]]
        + [(u, u["host_ns"] // 1000) for u in doc["slot_updates"]]
        + [(d, (d["delivered_ns"] - d["ready_ns"]) // 1000)
           for d in doc["deliveries"]])
    assert len(walls) > 30
    for record, wall_us in walls:
        assert 0 <= record["cpu_us"] <= wall_us + _CLOCK_SLACK_US, record
        # (the run-queue delay is the scheduler's own clock, a CPU's: on
        # this sandbox one reading ran 140 us past its stretch's wall, so
        # no single record is held to it)
        assert ("runq_us" in record) == readable
        assert record.get("runq_us", 0) >= 0
        assert "_ready" not in record
    # the two threads that clock themselves each opened one descriptor
    assert 1 <= len(_stepscope._sched_fds) <= 2 or not readable
    step_report = _load_script("step_report.py", "step_report_cpu")
    analysis = step_report.analyze(
        step_report.load_records(doc),
        deliveries=step_report.load_deliveries(doc),
        slot_updates=step_report.load_slot_updates(doc))
    m = analysis["models"]["gpt_engine"]
    assert m["phases"]["decode"]["off_cpu"]["off_cpu_ms"] >= 0
    assert "off_cpu_ms" in m["deliveries"]["decode"]
    rendered = step_report.render(analysis)
    assert "dispatching decode" in rendered
    assert "hand-overs off-cpu" in rendered
    _stepscope.configure(_stepscope.MODE_OFF)
    assert _stepscope._sched_fds == {}


def test_runq_is_absent_where_schedstat_cannot_be_read(monkeypatch):
    monkeypatch.setattr(_stepscope, "_SCHEDSTAT",
                        "/nonexistent/{tid}/schedstat")
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    began = _stepscope.clock()
    assert began[2] is None and began[1] > 0
    time.sleep(0.002)  # tpulint: disable=TPU001 - sync test, no loop
    ended = _stepscope.clock()
    _stepscope.loop_state("m", _stepscope.LOOP_ADMIT, began, ended, 2)
    _stepscope.slot_update("m", 1, 0, began, ended)
    rec = _stepscope.step_begin("m", _stepscope.PHASE_DECODE, 0)
    _stepscope.step_dispatched(rec)
    _stepscope.step_end(rec)
    doc = _stepscope.dump()
    for record in doc["records"] + doc["slot_updates"]:
        assert "cpu_us" in record and "runq_us" not in record
    assert _stepscope._sched_fds == {}
    step_report = _load_script("step_report.py", "step_report_norunq")
    cell = step_report._off_cpu(doc["records"], step_report._dispatch_us)
    assert "off_cpu_ms" in cell and "runq_ms" not in cell
    assert "run-queue" not in step_report._off_cpu_text(cell)
    # a dump from before the clocks: no cell, no text
    assert step_report._off_cpu([{"dispatch_us": 5}],
                                step_report._dispatch_us) is None
    assert step_report._off_cpu_text(None) == ""


def test_a_collection_is_recorded_while_stepscope_is_on_and_only_then():
    """The collector's hook: registered by ``configure``, one record a
    collection (when, how long, which generation, which thread), gone with
    stepscope; the ring stays for the readers until ``reset``."""
    gc.collect()
    assert _stepscope.dump()["gc"] == []
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    assert gc.callbacks.count(_stepscope._gc_hook) == 1
    _stepscope.configure(_stepscope.MODE_COUNTERS)      # once, not twice
    assert gc.callbacks.count(_stepscope._gc_hook) == 1
    before = time.monotonic_ns()
    gc.collect()
    after = time.monotonic_ns()
    full = [p for p in _stepscope.dump()["gc"] if p["generation"] == 2
            and before <= p["start_ns"] <= after]
    assert len(full) == 1
    (pause,) = full
    assert set(pause) == {"start_ns", "duration_ns", "generation",
                          "thread_ident", "thread_name"}
    assert 0 < pause["duration_ns"] <= after - before
    assert pause["thread_name"] == threading.current_thread().name
    assert pause["thread_ident"] == threading.get_ident()
    _stepscope.configure(_stepscope.MODE_OFF)
    assert _stepscope._gc_hook not in gc.callbacks
    n = len(_stepscope.dump()["gc"])
    gc.collect()
    assert len(_stepscope.dump()["gc"]) == n >= 1       # kept, not added to
    # the operator's row
    step_report = _load_script("step_report.py", "step_report_gc")
    doc = _stepscope.dump()
    assert step_report.load_pauses(doc) == doc["gc"]
    row = step_report._collector(doc["gc"], [])
    assert row["n"] == n and row["by_generation"]["2"] >= 1
    assert row["worst_ms"] <= row["total_ms"] and row["seconds"][0] == 0
    assert step_report._collector([], []) is None
    _stepscope.reset()
    assert _stepscope.dump()["gc"] == []
