"""95th percentile of every gap between consecutive tokens of a request,
over every request sent in the window that finished.

A whole-request reading on the client's clock, printed in every run (the
`latency:` line) and reported here from the traced run. It has no bound: this
closed loop keeps the chip busy throughout, and with a few tens of
multi-second requests in a window a tail is the few largest values (PERF.md
section 2 gives the spreads measured).
"""


from benchmarks.stats import percentile


def read(obs):
    gaps = obs.token_gaps_ms()
    return percentile(gaps, 95) if gaps else None
