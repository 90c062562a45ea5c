"""The reduction from a trace to busy time, operations and idle gaps: on
hand-made events, and on a small trace recorded on the chip
(benchmarks/record_small_trace.py -> data/small_trace.json)."""

import json
import os

import pytest

from benchmarks import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# One device: a `while` of 100..500 holding two body ops, then a lone op.
NESTED = [
    ("/device:TPU:0", "while", 100.0, 400.0),
    ("/device:TPU:0", "fusion.1", 120.0, 100.0),
    ("/device:TPU:0", "fusion.2", 250.0, 200.0),
    ("/device:TPU:0", "copy", 700.0, 100.0),
]


def test_busy_time_is_the_union_and_self_time_leaves_the_children_out():
    assert trace_reduce.busy_intervals(NESTED) == {
        "/device:TPU:0": [[100.0, 500.0], [700.0, 800.0]]}
    assert trace_reduce.self_times(NESTED) == {
        "while": 100.0, "fusion.1": 100.0, "fusion.2": 200.0, "copy": 100.0}


def test_reduce_cuts_to_the_span_and_labels_the_gaps():
    reduced = trace_reduce.reduce_trace(
        NESTED, (0.0, 1000.0), chips=1,
        host_spans=[(480.0, 560.0, "engine dispatching decode")])
    assert reduced["busy_s"] == pytest.approx(500e-9)
    assert reduced["window_s"] == pytest.approx(1000e-9)
    gaps = dict(map(tuple, reduced["idle_gaps"]))
    # 0..100 and 800..1000 have no host span; 500..700 starts inside one.
    assert gaps == {"engine between dispatches": pytest.approx(300e-9),
                    "engine dispatching decode": pytest.approx(200e-9)}
    assert reduced["device_ops"][0] == ["fusion.2", pytest.approx(200e-9)]
    cut = trace_reduce.reduce_trace(NESTED, (200.0, 750.0), chips=1)
    assert cut["busy_s"] == pytest.approx(350e-9)


def test_busy_time_is_averaged_over_the_chips_used():
    two = NESTED + [("/device:TPU:1", "copy", 0.0, 1000.0)]
    reduced = trace_reduce.reduce_trace(two, (0.0, 1000.0), chips=2)
    assert reduced["busy_s"] == pytest.approx((500 + 1000) / 2 * 1e-9)
    idle = trace_reduce.reduce_trace(NESTED, (0.0, 1000.0), chips=2)
    assert dict(map(tuple, idle["idle_gaps"]))["device never used"] == (
        pytest.approx(500e-9))
    assert len(trace_reduce.reduce_trace(
        [("d", f"op{i}", 10.0 * i, 5.0) for i in range(30)],
        (0.0, 300.0), chips=1)["device_ops"]) == 10


def test_the_small_recorded_trace():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        record = json.load(f)
    assert record["device"]["platform"] == "tpu"
    events = [tuple(e) for e in record["events"]]
    assert events and all(e[0].startswith("/device:TPU") for e in events)
    trace_at, perf_at = record["sync"]
    begun, ended = (t + trace_at - perf_at for t in record["span_perf_ns"])
    reduced = trace_reduce.reduce_trace(events, (begun, ended), chips=1)
    # Four runs of a small scan (about 13 us each) with 10 ms pauses: the
    # chip is idle nearly all the time. The first run began 1 ms before the
    # clock-sync annotation, so three of the four lie inside the span.
    assert reduced["window_s"] == pytest.approx((ended - begun) / 1e9)
    assert reduced["busy_s"] == pytest.approx(39.3e-6, rel=0.01)
    assert reduced["busy_s"] == pytest.approx(record["expected"]["busy_s"])
    whole = sum(end - start for intervals in
                trace_reduce.busy_intervals(events).values()
                for start, end in intervals) / 1e9
    assert whole == pytest.approx(4 / 3 * reduced["busy_s"], rel=0.05)
    # operations are named as the trace names them, by self time: the scan's
    # body, not the `while` that spans it
    ops = dict(map(tuple, reduced["device_ops"]))
    assert [n for n, _ in reduced["device_ops"]][:3] == record["expected"]["top_ops"]
    assert record["expected"]["top_ops"][0] == "fusion.13 bf16[256,512]"
    assert ops["while"] < 0.01 * ops["fusion.13 bf16[256,512]"]
    assert sum(ops.values()) == pytest.approx(whole, rel=1e-6)
    assert dict(map(tuple, reduced["idle_gaps"])) == {
        "engine between dispatches": pytest.approx(
            reduced["window_s"] - reduced["busy_s"])}


@pytest.mark.parametrize("name,short", [
    ("%convert.58 = f32[512,16,25,64]{3,2,1,0:T(8,128)} convert(bf16[512] %f)",
     "convert.58 f32[512,16,25,64]"),
    ("%while.3 = (s32[]{:T(128)}, bf16[8]{0}) while(%tuple)", "while.3"),
    ("dot_general.15", "dot_general.15"),
])
def test_operation_names_are_cut_to_name_and_shape(name, short):
    assert trace_reduce.short_name(name) == short


def test_reading_a_trace_made_here(tmp_path):
    """The CPU backend has no device plane: its operations (host-thread
    events that carry an ``hlo_op``) stand in for one in rehearsals."""
    import time

    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    begun = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_SYNC, mono_ns=begun):
        pass
    for _ in range(3):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = trace_reduce.read_xplane(str(tmp_path))
    assert raw["sync"] is not None and raw["sync"][1] == begun
    assert raw["events"] and raw["layout"]
    with pytest.raises(FileNotFoundError):
        trace_reduce.read_xplane(str(tmp_path / "nothing"))
