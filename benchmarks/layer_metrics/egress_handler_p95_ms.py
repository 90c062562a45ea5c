"""Token egress, the server's work for one message: from the moment the
stream's handler holds token i (`taken_ns[i]`) to the moment the response
generator is resumed behind the token's `yield` (`resumed_ns[i]`): the
core's response, `core_to_response`, the protobuf message and grpcio's send
of it, on the handler's thread. It overlaps `egress_wire_p95_ms`'s span (the
client may have the token before the handler asks for the next). 95th
percentile over every token of every request sent in the window that
finished."""

from benchmarks.host_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [resumed - taken for resumed, taken in
                                 zip(r["resumed_ns"], r["taken_ns"])])
