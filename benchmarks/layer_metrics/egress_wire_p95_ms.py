"""Token egress, its second part: from the moment the stream's handler
holds token i (`taken_ns[i]`) to the client's callback for it: the server's
response and message, the wire, the client's reader thread and its callback.
With `egress_wake_p95_ms`'s span it is a token's `egress` exactly. 95th
percentile over every token of every request sent in the window that
finished."""

from benchmarks.host_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [at - taken for at, taken in
                                 zip(log.token_ns, r["taken_ns"])])
