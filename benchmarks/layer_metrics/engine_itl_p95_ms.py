"""Engine scheduler: the gaps between consecutive hand-overs of one
request's tokens on the delivery thread (`out_ns`): `itl_p95_ms` with the
wire and the server core taken out. 95th percentile over every gap of every
request sent in the window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [b - a for a, b in
                                 zip(r["out_ns"], r["out_ns"][1:])])
