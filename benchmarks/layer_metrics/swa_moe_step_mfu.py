"""Whole step, window/global routed family: the operations this chip's share
of the model needs (``costs_swa_moe``: attention over the keys each kind of
layer attends, the dense layer, the shared expert, the router, and the
routed pairs an even router leaves on this chip: top-k x held / outputs a
layer) for every prompt and output token the window processed, over window
x chips x the chip's peak FLOP/s. Counted as ``mla_moe_step_mfu`` counts: an
output token when it is delivered inside the window, a prompt when its first
token is. None on a shape of another family."""

from benchmarks.costs_swa_moe import SwaMoeShape, attend_flops, token_flops


def _window_keys(s, first: int, last: int) -> float:
    """(row, key) pairs of rows ``first .. last - 1`` in a window layer: row
    i attends min(i + 1, window) keys."""
    full = max(last - max(first, s.window), 0)         # rows past the window
    ramp_to = min(last, s.window)
    ramp = (ramp_to * (ramp_to + 1) - first * (first + 1)) / 2 if (
        first < ramp_to) else 0
    return ramp + full * s.window


def read(obs):
    s = obs.shape
    if obs.peaks is None or not isinstance(s, SwaMoeShape):
        return None
    start, end = obs.window["start_ns"], obs.window["end_ns"]
    flops = 0.0
    for log in obs.finished():
        prompt = log.request.prompt.shape[1]
        if start <= log.token_ns[0] < end:
            # Row i attends i + 1 positions; the head runs for the last row.
            flops += prompt * token_flops(s, with_head=False)
            flops += attend_flops(s, prompt * (prompt + 1) / 2,
                                  _window_keys(s, 0, prompt))
            flops += 2.0 * s.d_model * s.vocab_size
        for j, at in enumerate(log.token_ns[1:], start=1):
            if start <= at < end:
                flops += token_flops(s) + attend_flops(
                    s, prompt + j, min(prompt + j, s.window))
    if not flops:
        return None
    return 100.0 * flops / (obs.window_s * obs.chips
                            * obs.peaks["flops_per_s"])
