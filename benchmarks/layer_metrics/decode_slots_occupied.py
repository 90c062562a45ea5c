"""Engine scheduler: of the decode slot-steps the window dispatched, the
share that carried a request (stepscope decode records: sum of batch_size x
micro_steps over sum of slots x micro_steps)."""


def read(obs):
    decode = obs.decode_steps()
    offered = sum(r["slots"] * r["micro_steps"] for r in decode)
    if not offered:
        return None
    used = sum(r["batch_size"] * r["micro_steps"] for r in decode)
    return 100.0 * used / offered
