"""Engine scheduler: the share of the window the engine loop spent waiting
for a dispatch ticket (stepscope's `ticket_wait` loop state: inside
`try_ticket` with the in-flight window full): how far the host runs ahead of
the chip. A diagnostic with no preferred direction of its own: it is read
beside `device_idle_share` and the other loop states. At an unchanged device
step a higher share says the host is less in the way (the `better: higher`
that BENCHMARK.json has to state); a change that shortens the step lowers it
by design, and that fall is the step's gain showing, not a loss: what then
matters is which state took the share (`join`, `admit`, dispatching).
Stretches are cut to the window. A program whose records carry no loop
states (from before it had them) reports nothing."""


def read(obs):
    if not obs.window_s or not any("lanes" in r for r in obs.steps):
        return None
    lo, hi = obs.window["start_ns"], obs.window["end_ns"]
    waited = 0.0
    for r in obs.steps:
        if r["phase"] == "ticket_wait":
            start = r["start_ns"]
            end = start + r["dispatch_us"] * 1e3
            waited += max(min(end, hi) - max(start, lo), 0.0)
    return 100.0 * waited / (hi - lo)
