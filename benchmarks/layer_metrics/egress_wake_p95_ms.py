"""Token egress, its first part: from the delivery thread's hand-over of
token i (`_Distributor._deliver`, `out_ns[i]`) to the moment the stream's
handler holds it (`taken_ns[i]`, stamped in `GptEngineModel.infer`'s
generator when `req.out.get` has returned): a condition wake-up and the
interpreter lock. With `egress_wire_p95_ms`'s span it is a token's `egress`
exactly. 95th percentile over every token of every request sent in the
window that finished."""

from benchmarks.host_spans import span_percentile_ms


def read(obs):
    return span_percentile_ms(
        obs, 95, lambda log, r: [taken - out for taken, out in
                                 zip(r["taken_ns"], r["out_ns"])])
