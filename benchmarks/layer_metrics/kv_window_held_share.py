"""KV cache by kind: what the window layers hold for the requests of a
dispatch (stepscope ``kv_held_window_bytes``: a ring's pages a request and
no more) over what they would hold if they kept every position as the
global layers do (``kv_held_global_bytes`` x window layers / global
layers), at the dispatch where the requests held the most. Lower = the
window layers' cache stopped growing with the context. None where no record
splits the cache by kind (a family without window layers)."""

from benchmarks.costs_swa_moe import SwaMoeShape


def read(obs):
    s = obs.shape
    records = [r for r in obs.steps if r.get("kv_held_global_bytes")]
    if not records or not isinstance(s, SwaMoeShape):
        return None
    peak = max(records, key=lambda r: r["kv_held_global_bytes"])
    as_global = (peak["kv_held_global_bytes"] * s.layers_of("window")
                 / s.layers_of("global"))
    return 100.0 * peak["kv_held_window_bytes"] / as_global
