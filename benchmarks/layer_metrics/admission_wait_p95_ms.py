"""Engine scheduler: from the request's entry into the admission queue to
the moment `_admit_requests` has given it a slot and its pages: the wait for
the engine loop to come round (it may be inside a dispatch call) and for a
slot or pages to come free. 95th percentile over every request sent in the
window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [r["admitted_ns"] - r["submit_ns"]])
