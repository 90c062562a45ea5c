"""TPU010: JAX hot-path hazard detection.

The stepscope numbers that motivate this rule: at tp=2 the decode loop
spends 354.8 ms in host dispatch against 5.3 ms of device time — the
regime where one hidden device→host sync or one silent retrace costs
more than the step itself. This rule makes those
hazards lint errors *on the hot paths only*, so cold setup/debug code
stays free to coerce arrays however it likes.

**Hot regions** are declared, not guessed: annotate a function with
``# tpulint: hot-path`` on (or immediately above) its ``def`` line, and
everything reachable from it in the project call graph is hot. The
in-tree roots are the engines' decode/step loops, the distributor
delivery loop, and the shm upload path.

Flagged inside hot regions (``_callgraph.py`` records the candidates via
local device-taint dataflow — results of ``jax.*``/``jnp.*``/``lax.*``
calls, jitted-callable results, ``jax.Array``-annotated parameters):

* **host syncs** — ``np.asarray``/``np.array``/``float``/``int``/
  ``bool``/``.item()``/``.tolist()`` on a device value, and
  ``jax.device_get``;
* **bool syncs** — ``if``/``while`` branching on a device value
  (identity checks ``is None`` excluded: metadata never transfers);
* **blocking syncs** — ``block_until_ready`` in a dispatch path;
* **retrace triggers** — ``jax.jit``/``jax.pmap`` constructed inside a
  hot function body (a fresh callable retraces per call; construction
  under a cache-miss guard like ``if key not in cache:`` is recognized
  as the memoized-build idiom and skipped), and jitted callables with
  ``static_argnums``/``static_argnames`` invoked with a loop-varying
  argument (every distinct value recompiles).

Deliberate sync points — the single designed readback per decode step,
idle-only warmup barriers — suppress with ``# tpulint: disable=TPU010``
and a justification, which doubles as documentation of where the
device→host boundary intentionally sits.
"""

from typing import List, Optional, Sequence

from tritonclient_tpu.analysis import _callgraph
from tritonclient_tpu.analysis._engine import FileContext, Finding, Rule


class JaxHazardRule(Rule):
    id = "TPU010"
    name = "jax-hot-path"
    description = (
        "device->host sync or retrace trigger on a `# tpulint: hot-path` "
        "reachable function (dispatch-bound decode loops cannot afford "
        "either)"
    )

    def check_project(self, ctxs: Sequence[FileContext]) -> List[Finding]:
        if not ctxs:
            return []
        graph = _callgraph.get_callgraph(ctxs)
        linted = {ctx.path for ctx in ctxs}
        findings: List[Finding] = []
        for key in sorted(graph.functions):
            fn = graph.functions[key]
            if fn.path not in linted:
                continue
            root = graph.hot_root(key)
            if root is None:
                continue
            via = "" if root == key else f", hot via `{root}`"
            for hz in fn.hazards:
                msg = _message(hz, via)
                if msg is None:
                    continue
                findings.append(Finding(
                    JaxHazardRule.id, fn.path, hz.line, hz.col, msg))
        findings.sort(key=lambda f: (f.path, f.line, f.col))
        return findings


def _message(hz, via: str) -> Optional[str]:
    loop = " inside a loop" if hz.in_loop else ""
    if hz.kind == "host-sync":
        return (f"device->host sync in hot path{loop}: {hz.detail}"
                f"{via}")
    if hz.kind == "bool-sync":
        return f"{hz.detail} in hot path{loop}{via}"
    if hz.kind == "block-sync":
        return (f"{hz.detail} in hot path{loop} — stalls the dispatch "
                f"pipeline{via}")
    if hz.kind == "jit-in-body":
        if hz.guarded:
            return None  # cache-miss-guarded build: compiles once
        return f"retrace trigger in hot path{loop}: {hz.detail}{via}"
    if hz.kind == "static-drift":
        return f"retrace trigger in hot path: {hz.detail}{via}"
    return None
