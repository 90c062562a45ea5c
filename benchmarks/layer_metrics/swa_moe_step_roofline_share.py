"""Model step / kernels, window/global routed family: the least time the
chip could have taken for the dispatches of the traced span, over the time
the device was busy in it.

Per dispatch the least time is max(operations / peak FLOP/s, needed bytes /
peak bytes/s), from the dispatch's own stepscope record (``costs_swa_moe.
dispatch_work``): the weights outside the routed experts once a micro-step,
each HELD expert that got a token once (``experts_hit``), and the pages the
attention has to read by kind (``ctx_pages_global`` / ``ctx_pages_window``:
a window layer's are its window's). None on a shape of another family or
where no record carries the counters."""

from benchmarks.costs_swa_moe import (SwaMoeShape, dispatch_work,
                                      roofline_seconds)


def read(obs):
    s = obs.shape
    if (obs.peaks is None or obs.trace is None or not obs.trace["busy_s"]
            or not isinstance(s, SwaMoeShape)):
        return None
    block = int(obs.cell.config["engine"]["block_size"])
    lo, hi = obs.trace["span_ns"]
    works = [dispatch_work(s, r, block) for r in obs.steps
             if lo <= r["start_ns"] < hi]
    least = sum(roofline_seconds(w, obs.peaks) for w in works if w)
    if not least:
        return None
    return 100.0 * least / (obs.trace["busy_s"] * obs.chips)
