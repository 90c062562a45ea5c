"""Engine scheduler: from admission to the hand-over of the first token:
the request's prefill chunks, the decode dispatches the loop puts between
them, the device's queue ahead of them, the readback and the delivery
thread's turn (`first_ready_ns` on the record splits off the hand-over).
Median over every request sent in the window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 50, lambda log, r: [r["out_ns"][0] - r["admitted_ns"]])
