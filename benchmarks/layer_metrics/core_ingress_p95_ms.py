"""Server core: from the server's receipt of the request to its entry into
the engine's admission queue (`GenerationEngine.submit`): parse, the stream
pool's hand-over to a worker, input resolution and validation, the model's
`infer` up to the generator's first consumption. 95th percentile over every
request sent in the window that finished."""

from benchmarks.request_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [r["submit_ns"] - r["recv_ns"]])
