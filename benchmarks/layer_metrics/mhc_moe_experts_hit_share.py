"""Routed expert layer under the multi-stream residual path: of the experts
a decode dispatch holds (expert layers x micro-steps x experts), the share
that got a token, mean over the window's decode dispatches (stepscope
``experts_hit`` / ``experts_held``): ``moe_experts_hit_share``'s reading in
this configuration's cell (64 experts, top 4, every expert held). None on a
shape of another family or where no decode record carries routing
counters."""

from benchmarks.costs_mhc_mla_moe import MhcMlaMoeShape
from benchmarks.layer_metrics import moe_experts_hit_share


def read(obs):
    if not isinstance(obs.shape, MhcMlaMoeShape):
        return None
    return moe_experts_hit_share.read(obs)
