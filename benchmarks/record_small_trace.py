"""Records the small trace the reduction is checked on (tests/benchmark/data).

    chiprun -- python3 benchmarks/record_small_trace.py chiprun_out/small_trace.json

A scanned two-matmul body, run a few times with pauses between, traced as a
benchmark run traces; the device events are kept as plain tuples with the
trace's layout, so the test needs no profiler and no chip.
"""

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks import trace_reduce

    def body(x, w):
        return jnp.tanh(x @ w), None

    @jax.jit
    def step(x, ws):
        return lax.scan(body, x, ws)[0]

    x = jnp.ones((256, 512), jnp.bfloat16)
    ws = jnp.ones((6, 512, 512), jnp.bfloat16) * 0.01
    step(x, ws).block_until_ready()
    with tempfile.TemporaryDirectory(dir=ROOT) as directory:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(directory, profiler_options=options)
        begun = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_SYNC,
                                          mono_ns=begun):
            pass
        for _ in range(4):
            step(x, ws).block_until_ready()
            time.sleep(0.01)
        ended = time.perf_counter_ns()
        jax.profiler.stop_trace()
        raw = trace_reduce.read_xplane(directory)
    device = jax.devices()[0]
    record = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "span_perf_ns": [begun, ended],
        "sync": raw["sync"],
        "layout": raw["layout"],
        "events": raw["events"],
    }
    out = os.path.join(ROOT, sys.argv[1])
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f)
    print(f"{len(raw['events'])} device events, layout {raw['layout']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
