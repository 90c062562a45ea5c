"""Model step / kernels: the least time the chip could have taken for the
dispatches of the traced span, over the time the device was busy in it.

Per dispatch (stepscope counts: phase, lanes or active slots, micro-steps)
the least time is max(operations / peak FLOP/s, needed bytes / peak bytes/s)
with needed bytes = the weights once per step + the K/V the requests really
hold, not the table width the program gathers. The context a dispatch holds
is not in its record, so the span's means are used: over the tokens
delivered in the span for decode, over the chunks of the prompts sent in it
for prefill. The bytes term is linear in context and decode is bound by
bytes at these batch sizes, so the means lose nothing there.
"""

from benchmarks.costs import (decode_dispatch, prefill_dispatch,
                              roofline_seconds)


def read(obs):
    if obs.peaks is None or obs.trace is None or not obs.trace["busy_s"]:
        return None
    lo, hi = obs.trace["span_ns"]
    steps = [r for r in obs.steps if lo <= r["start_ns"] < hi]
    if not steps:
        return None
    s = obs.shape
    chunk = int(obs.cell.config["engine"]["prefill_chunk"])
    decode_contexts, chunk_tokens, chunk_contexts = [], [], []
    for log in obs.finished():
        prompt = log.request.prompt.shape[1]
        decode_contexts += [prompt + j
                            for j, at in enumerate(log.token_ns[1:], start=1)
                            if lo <= at < hi]
        if lo <= log.sent_ns < hi:
            for start in range(0, prompt, chunk):
                upto = min(start + chunk, prompt)
                chunk_tokens.append(upto - start)
                chunk_contexts.append(upto)
    mean = lambda xs, default: sum(xs) / len(xs) if xs else default  # noqa: E731
    least = 0.0
    for r in steps:
        if r["phase"] == "decode":
            work = decode_dispatch(s, r["batch_size"], r["micro_steps"],
                                   mean(decode_contexts, 0.0))
        elif r["phase"] == "prefill_chunk":
            work = prefill_dispatch(s, r["batch_size"],
                                    mean(chunk_tokens, float(chunk)),
                                    mean(chunk_contexts, float(chunk)))
        else:
            continue
        least += roofline_seconds(work, obs.peaks)
    return 100.0 * least / (obs.trace["busy_s"] * obs.chips)
