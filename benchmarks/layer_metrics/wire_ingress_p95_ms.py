"""Client + wire: from the client's send (`async_stream_infer` about to be
called) to the server's receipt of the request (`server/_grpc.py` `t_recv`,
the request's `REQUEST_RECV`, copied onto stepscope's request record): the
client's serialisation, the gRPC stream and the server's feeder thread.
95th percentile over every request sent in the window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [r["recv_ns"] - log.sent_ns])
