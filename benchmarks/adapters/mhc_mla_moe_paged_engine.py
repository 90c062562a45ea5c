"""Adapter ``mhc_mla_moe_paged_engine``: the MLA / routed-expert family with
a multi-stream residual path (mHC) and YaRN-scaled rotary positions, behind
the same paged generation engine and in-process gRPC server as the other
families. A configuration selects it by ``"adapter"``; the harness finds
this file by that name and uses only what ``__all__`` lists.

Nothing here measures. The engine model, the warm-up (the prefill lane x
context family of the mix's prompt lengths, decode with its fused widths)
and the take-down are ``mla_moe_paged_engine``'s: the family served is the
same ``MlaMoeEngineModel``; what differs is the program's configuration (the
residual and scaling fields), the weights' maps and the reference.
"""

from benchmarks.adapters import mla_moe_paged_engine as _base
from benchmarks.costs_mhc_mla_moe import MhcMlaMoeShape
from benchmarks.costs_mhc_mla_moe import mhc_mla_moe_shape as shape_of  # noqa: F401 - adapter API
from benchmarks.reference_mhc_mla_moe import check_outputs  # noqa: F401 - adapter API
from benchmarks.weights_mhc_mla_moe import make_weights  # noqa: F401 - adapter API
# The program's part: a checkout without it (the parent of the PR that added
# the residual path) fails here, before any weight is made.
from tritonclient_tpu.models import mhc  # noqa: F401
from tritonclient_tpu.models import mla_moe

__all__ = ["shape_of", "make_weights", "Serving", "check_outputs"]


def program_config(shape: MhcMlaMoeShape) -> "mla_moe.MlaMoeConfig":
    import dataclasses

    scaling = None
    if shape.yarn_factor > 1:
        scaling = mla_moe.YarnScaling(
            factor=shape.yarn_factor,
            original_max_len=shape.yarn_original_positions,
            beta_fast=shape.yarn_beta_fast, beta_slow=shape.yarn_beta_slow,
            mscale=shape.yarn_mscale,
            mscale_all_dim=shape.yarn_mscale_all_dim)
    return dataclasses.replace(
        _base.program_config(shape), rope_scaling=scaling,
        hc_mult=shape.hc_mult, hc_sinkhorn_iters=shape.hc_sinkhorn_iters,
        hc_eps=shape.hc_eps,
        hc_res_clamp=(shape.hc_res_clamp_min, shape.hc_res_clamp_max))


class Serving(_base.Serving):
    """The model, its engine and the gRPC front end, in this process."""

    def __init__(self, shape: MhcMlaMoeShape, weights: dict,
                 engine_settings: dict, chips: int = 1):
        from tritonclient_tpu.server import InferenceServer

        if chips != 1:
            raise ValueError("the MLA/MoE family is served on one chip")
        self.shape = shape
        self.model = mla_moe.MlaMoeEngineModel(
            program_config(shape), params=weights,
            max_slots=int(engine_settings["max_slots"]),
            block_size=int(engine_settings["block_size"]),
            n_blocks=engine_settings.get("n_blocks"),
            prefill_chunk=int(engine_settings["prefill_chunk"]))
        self.engine = self.model.engine
        self.model_name = self.model.name
        self._server = InferenceServer(models=[self.model], http=False)
        self._server.start()
        self.address = self._server.grpc_address
