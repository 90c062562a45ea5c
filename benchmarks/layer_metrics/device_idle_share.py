"""Device: the share of the traced span in which no operation ran on the
chip (1 - union of device-operation intervals / span), from the profiler's
trace as benchmarks/trace_reduce.py reduces it. Off the chip there is no
device to be idle and nothing is reported."""


def read(obs):
    if obs.peaks is None or obs.trace is None or not obs.trace["window_s"]:
        return None
    return 100.0 * (1.0 - obs.trace["busy_s"] / obs.trace["window_s"])
