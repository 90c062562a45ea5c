"""Model step / kernels, MLA / routed-expert family with a multi-stream
residual path: the least time the chip could have taken for the dispatches
of the traced span, over the time the device was busy in it.

Per dispatch the least time is max(operations / peak FLOP/s, needed bytes /
peak bytes/s), from the dispatch's own stepscope record
(``costs_mhc_mla_moe.dispatch_work``): the weights outside the experts once a
micro-step, each expert that got a token once (``experts_hit``), the latent
cache the requests hold (``ctx_tokens``), and the maps' three passes over
the streams of the rows that passed them (``hc_rows``). None on a shape of
another family or where no record carries the counters."""

from benchmarks.costs_mhc_mla_moe import (MhcMlaMoeShape, dispatch_work,
                                          roofline_seconds)


def read(obs):
    s = obs.shape
    if (obs.peaks is None or obs.trace is None or not obs.trace["busy_s"]
            or not isinstance(s, MhcMlaMoeShape)):
        return None
    lo, hi = obs.trace["span_ns"]
    works = [dispatch_work(s, r) for r in obs.steps
             if lo <= r["start_ns"] < hi]
    least = sum(roofline_seconds(w, obs.peaks) for w in works if w)
    if not least:
        return None
    return 100.0 * least / (obs.trace["busy_s"] * obs.chips)
