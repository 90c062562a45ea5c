"""Host process: the share of the window spent inside collections of the
interpreter's collector, on any thread (stepscope's `gc.callbacks` hook,
`dump()["gc"]`: start and duration of each). A collection holds the
interpreter lock, so every thread of the process stalls for it, the engine
loop included. Pauses are cut to the window. A program that keeps no such
ring reports nothing."""

from benchmarks.host_spans import collector_pauses


def read(obs):
    pauses = collector_pauses()
    if pauses is None or not obs.window_s:
        return None
    lo, hi = obs.window["start_ns"], obs.window["end_ns"]
    paused = sum(max(min(end, hi) - max(start, lo), 0)
                 for start, end in pauses)
    return 100.0 * paused / (hi - lo)
