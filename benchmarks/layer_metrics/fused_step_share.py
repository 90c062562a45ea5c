"""Engine scheduler: of all decode micro-steps, the share issued in fused
dispatches (micro_steps > 1), which deliver their tokens in one burst."""


def read(obs):
    decode = obs.decode_steps()
    total = sum(r["micro_steps"] for r in decode)
    if not total:
        return None
    fused = sum(r["micro_steps"] for r in decode if r["micro_steps"] > 1)
    return 100.0 * fused / total
