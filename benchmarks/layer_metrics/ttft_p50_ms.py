"""Send to first streamed token, median over every request sent in the
window that finished (a failed request has no latency; it is in `failed`).

A whole-request reading on the client's clock, printed in every run (the
`latency:` line) and reported here from the traced run. It has no bound: this
closed loop keeps the chip busy throughout, and with a few tens of
multi-second requests in a window a tail is the few largest values (PERF.md
section 2 gives the spreads measured).
"""


from benchmarks.stats import percentile


def read(obs):
    waits = obs.first_token_waits_ms()
    return percentile(waits, 50) if waits else None
