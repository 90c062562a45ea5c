"""Routed expert layer, a chip's share of it: of the HELD experts a decode
dispatch could read (expert layers x micro-steps x experts held), the share
that got a token, mean over the window's decode dispatches (stepscope
``experts_hit`` / ``experts_held``; a chosen expert that another chip holds
counts nowhere here). It is what decides a decode step's needed bytes.
None on a shape of another family or where no decode record carries routing
counters."""

from benchmarks.costs_swa_moe import SwaMoeShape
from benchmarks.layer_metrics import moe_experts_hit_share


def read(obs):
    if not isinstance(obs.shape, SwaMoeShape):
        return None
    return moe_experts_hit_share.read(obs)
