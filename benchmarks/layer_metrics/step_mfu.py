"""Whole step: the model's operations for every prompt and output token the
window processed, over window x chips x the chip's peak FLOP/s.

An output token counts when it is delivered inside the window; a prompt
counts when its first token is. Recomputed or padded work does not count.
"""

from benchmarks.costs import token_flops


def read(obs):
    if obs.peaks is None:
        return None
    start, end = obs.window["start_ns"], obs.window["end_ns"]
    s = obs.shape
    flops = 0.0
    for log in obs.finished():
        prompt = log.request.prompt.shape[1]
        if start <= log.token_ns[0] < end:
            # Row i attends i + 1 keys; sum over rows of the attention term.
            flops += prompt * token_flops(s, 0, with_head=False)
            flops += s.n_layer * 4 * s.d_model * prompt * (prompt + 1) / 2
            flops += 2 * s.d_model * s.vocab_size
        for j, at in enumerate(log.token_ns[1:], start=1):
            if start <= at < end:
                flops += token_flops(s, prompt + j)
    if not flops:
        return None
    return 100.0 * flops / (obs.window_s * obs.chips
                            * obs.peaks["flops_per_s"])
