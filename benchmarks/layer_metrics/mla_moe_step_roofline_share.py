"""Model step / kernels, MLA / routed-expert family: the least time the chip
could have taken for the dispatches of the traced span, over the time the
device was busy in it.

Per dispatch the least time is max(operations / peak FLOP/s, needed bytes /
peak bytes/s), from the dispatch's own stepscope record (``costs_mla_moe.
dispatch_work``): the weights outside the experts once a micro-step, each
expert that got a token once (``experts_hit``, from the step's histogram),
and the latent cache the requests hold (``ctx_tokens``). The PR that added
the family wrote no kernel of its own (the grouped product is the
compiler's), so this share stands for the expert layer's too. None on a
shape of another family or where no record carries routing counters."""

from benchmarks.costs_mla_moe import (MlaMoeShape, dispatch_work,
                                      roofline_seconds)


def read(obs):
    s = obs.shape
    if (obs.peaks is None or obs.trace is None or not obs.trace["busy_s"]
            or not isinstance(s, MlaMoeShape)):
        return None
    lo, hi = obs.trace["span_ns"]
    works = [dispatch_work(s, r) for r in obs.steps
             if lo <= r["start_ns"] < hi]
    least = sum(roofline_seconds(w, obs.peaks) for w in works if w)
    if not least:
        return None
    return 100.0 * least / (obs.trace["busy_s"] * obs.chips)
