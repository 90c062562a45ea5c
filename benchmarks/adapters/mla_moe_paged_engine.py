"""Adapter ``mla_moe_paged_engine``: a DeepSeek-V3-shaped decoder (latent
attention, routed experts with a shared one, rotary positions) behind the
same paged generation engine and in-process gRPC server as the GPT family.
A configuration selects it by ``"adapter"``; the harness finds this file by
that name and uses only what ``__all__`` lists.

Nothing here measures. What differs from ``gpt_paged_engine`` is the model
the engine is given, the pools it is warmed and taken down by (the engine's
own, however many the family has), and the reference that checks it; the
requests that warm it, the idle wait and the pool reading are that
adapter's.
"""

from typing import List

from benchmarks.adapters.gpt_paged_engine import Serving as _GptServing
from benchmarks.adapters.gpt_paged_engine import prefill_context_blocks
from benchmarks.costs_mla_moe import MlaMoeShape
from benchmarks.costs_mla_moe import mla_moe_shape as shape_of  # noqa: F401 - adapter API
from benchmarks.reference_mla_moe import check_outputs  # noqa: F401 - adapter API
from benchmarks.weights_mla_moe import make_weights  # noqa: F401 - adapter API
# The program's part: a checkout without it (the parent of the PR that
# added this family) fails here, before any weight is made.
from tritonclient_tpu.models import mla_moe

__all__ = ["shape_of", "make_weights", "Serving", "check_outputs"]


def program_config(shape: MlaMoeShape) -> "mla_moe.MlaMoeConfig":
    import jax.numpy as jnp

    return mla_moe.MlaMoeConfig(
        vocab_size=shape.vocab_size, d_model=shape.d_model,
        n_layers=shape.n_layer, n_dense_layers=shape.n_dense_layer,
        n_heads=shape.n_head, q_lora_rank=shape.q_lora_rank,
        kv_lora_rank=shape.kv_lora_rank,
        qk_nope_head_dim=shape.qk_nope_head_dim,
        qk_rope_head_dim=shape.qk_rope_head_dim,
        v_head_dim=shape.v_head_dim, d_ff=shape.d_ff,
        n_experts=shape.n_experts,
        experts_per_token=shape.experts_per_token, d_expert=shape.d_expert,
        n_shared_experts=shape.n_shared_experts,
        routed_scaling_factor=shape.routed_scaling_factor,
        rope_theta=shape.rope_theta, rms_norm_eps=shape.rms_norm_eps,
        max_len=shape.n_positions, dtype=jnp.dtype(shape.dtype))


class Serving(_GptServing):
    """The model, its engine and the gRPC front end, in this process."""

    def __init__(self, shape: MlaMoeShape, weights: dict,
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

    def warm(self, mix: dict) -> dict:
        """Compile or load every executable the mix's window will run: the
        admission scatters, the prefill (lane x context) family of the
        mix's prompt lengths, the slices the engine takes of a prefill's
        result, and decode with its fused widths. The warming prompts are
        one chunk long and a little more, so they stay inside that family."""
        import jax

        engine = self.engine
        chunk = engine.prefill_chunk
        blocks = prefill_context_blocks(mix, engine.block_size, chunk)
        engine.warm_admission()
        engine.warm_prefill(ctx_blocks=blocks)
        self._warm_first_token_slices(min(blocks))
        # One request alone with 8 tokens to make: the prefill gives the
        # first, then 7 are owed: a fused window of 4, one of 2, one step.
        self._drive([self._request(chunk, 8)])
        # All slots at once: a full bank's decode and its completions.
        self._drive([self._request(chunk + 8 * i, 6 + i)
                     for i in range(engine.max_slots)])
        jax.block_until_ready(engine._pools)
        return {"prefill_context_blocks": blocks}

    def _warm_first_token_slices(self, context_blocks: int):
        # ``firsts[i:i+1]`` off a prefill dispatch's own result, for each
        # lane bucket: one tiny executable per (lanes, i).
        import jax.numpy as jnp

        engine = self.engine
        n_ctx = 1       # the bucket ``warm_prefill`` made for this context
        while n_ctx < context_blocks:
            n_ctx *= 2
        lanes_family: List[int] = [1]
        while lanes_family[-1] < engine.max_slots:
            lanes_family.append(min(lanes_family[-1] * 2, engine.max_slots))
        for lanes in lanes_family:
            z = jnp.zeros((lanes,), jnp.int32)
            with engine._cv:
                (firsts,), _ = engine._keep_pools(engine._prefill_chunk_fn(
                    engine.params, *engine._pools,
                    jnp.zeros((lanes, engine.prefill_chunk), jnp.int32),
                    jnp.zeros((lanes, n_ctx), jnp.int32),
                    z, jnp.ones((lanes,), jnp.int32), z,
                    jnp.zeros((lanes,), jnp.float32), z), 1)
                for i in range(lanes):
                    firsts[i:i + 1].block_until_ready()

    def close(self) -> None:
        """Stop the front end and the engine, and free the page pool."""
        self._server.stop()
        self.engine.shutdown()
        self.engine.release_pools()
