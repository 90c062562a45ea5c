"""Whole step, MLA / routed-expert family with a multi-stream residual path:
the ACTIVE operations (the experts a token is routed to, attention in its
expanded form, the maps' projections: ``costs_mhc_mla_moe.token_flops``) for
every prompt and output token the window processed, over window x chips x
the chip's peak FLOP/s. Counted as ``mla_moe_step_mfu`` counts: an output
token when it is delivered inside the window, a prompt when its first token
is. None on a shape of another family."""

from benchmarks.costs_mhc_mla_moe import (MhcMlaMoeShape, attend_flops,
                                          token_flops)


def read(obs):
    s = obs.shape
    if obs.peaks is None or not isinstance(s, MhcMlaMoeShape):
        return None
    start, end = obs.window["start_ns"], obs.window["end_ns"]
    flops = 0.0
    for log in obs.finished():
        prompt = log.request.prompt.shape[1]
        if start <= log.token_ns[0] < end:
            # Row i attends i + 1 positions; the head runs for the last row.
            flops += prompt * token_flops(s, 0, with_head=False)
            flops += attend_flops(s, prompt * (prompt + 1) / 2)
            flops += 2.0 * s.d_model * s.vocab_size
        for j, at in enumerate(log.token_ns[1:], start=1):
            if start <= at < end:
                flops += token_flops(s, prompt + j)
    if not flops:
        return None
    return 100.0 * flops / (obs.window_s * obs.chips
                            * obs.peaks["flops_per_s"])
