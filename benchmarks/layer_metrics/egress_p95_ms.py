"""Token egress, one span: from the delivery thread's hand-over of token i
(`_Distributor._deliver`, `out_ns[i]`) to the client's callback for it: the
model's generator, the core's response, the gRPC stream and the client's
reader thread. 95th percentile over every token of every request sent in
the window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [at - out for at, out in
                                 zip(log.token_ns, r["out_ns"])])
