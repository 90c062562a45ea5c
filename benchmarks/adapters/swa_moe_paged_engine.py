"""Adapter ``swa_moe_paged_engine``: a grouped-query decoder of window and
global layers with routed experts of which the chip holds a share, behind
the same paged generation engine and in-process gRPC server as the other
families. A configuration selects it by ``"adapter"``; the harness finds
this file by that name and uses only what ``__all__`` lists.

Nothing here measures. What differs from ``mla_moe_paged_engine`` is the
model the engine is given and the reference that checks it, and that the
family's prefill is not specialised by its context (the kernel's grid is
traced): the warm-up gives every lane bucket the whole table and nothing
else, whatever the mix's prompt lengths.
"""

from benchmarks.adapters.mla_moe_paged_engine import Serving as _MlaServing
# The program's part: a checkout without it (the parent of the PR that
# added this family) fails here, before any weight is made.
from tritonclient_tpu.models import swa_moe
from benchmarks.costs_swa_moe import SwaMoeShape
from benchmarks.costs_swa_moe import swa_moe_shape as shape_of  # noqa: F401 - adapter API
from benchmarks.reference_swa_moe import check_outputs  # noqa: F401 - adapter API
from benchmarks.weights_swa_moe import make_weights  # noqa: F401 - adapter API

__all__ = ["shape_of", "make_weights", "Serving", "check_outputs"]


def program_config(shape: SwaMoeShape) -> "swa_moe.SwaMoeConfig":
    import jax.numpy as jnp

    return swa_moe.SwaMoeConfig(
        vocab_size=shape.vocab_size, d_model=shape.d_model,
        n_layers=shape.n_layer, n_dense_layers=shape.n_dense_layer,
        n_heads=shape.n_head, n_kv_heads=shape.n_kv_head,
        head_dim=shape.head_dim, window=shape.window,
        layer_kinds=shape.layer_kinds, d_ff=shape.d_ff,
        n_experts=shape.n_experts, experts_held=shape.experts_held,
        first_expert=shape.first_expert,
        experts_per_token=shape.experts_per_token, d_expert=shape.d_expert,
        n_shared_experts=shape.n_shared_experts,
        routed_scaling_factor=shape.routed_scaling_factor,
        rope_theta=shape.rope_theta, rms_norm_eps=shape.rms_norm_eps,
        max_len=shape.n_positions, dtype=jnp.dtype(shape.dtype))


class Serving(_MlaServing):
    """The model, its engine and the gRPC front end, in this process."""

    def __init__(self, shape: SwaMoeShape, weights: dict,
                 engine_settings: dict, chips: int = 1):
        from tritonclient_tpu.server import InferenceServer

        if chips != 1:
            raise ValueError("the window/global routed family is served on "
                             "one chip")
        self.shape = shape
        self.model = swa_moe.SwaMoeEngineModel(
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

    def warm(self, mix: dict) -> dict:
        """Compile or load every executable the mix's window will run: the
        slot-state update, the prefill program of each lane bucket (one a
        bucket: the whole table, whatever the context), and decode with its
        fused widths. The warming prompts are one chunk long and a little
        more, so they cross a chunk's edge."""
        import jax

        engine = self.engine
        chunk = engine.prefill_chunk
        engine.warm_admission()
        engine.warm_prefill()
        # One request alone with 8 tokens to make: the prefill gives the
        # first, then 7 are owed: a fused window of 4, one of 2, one step.
        self._drive([self._request(chunk, 8)])
        # All slots at once: a full bank's decode and its completions.
        self._drive([self._request(chunk + 8 * i, 6 + i)
                     for i in range(engine.max_slots)])
        jax.block_until_ready(engine._pools)
        return {"prefill_programs": "one a lane bucket, the whole table"}
