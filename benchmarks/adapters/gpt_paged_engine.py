"""Adapter ``gpt_paged_engine``: a GPT-2-shaped decoder behind the paged
generation engine and the in-process gRPC server, as the benchmark takes it
from the program. A configuration selects it by ``"adapter"``; the harness
finds this file by that name and uses only what ``__all__`` lists.

Nothing here measures. It builds the served model from the benchmark's own
weights, warms exactly the shapes a mix will use, and takes the whole thing
down again so the reference can have the device. With ``chips`` > 1 the
engine runs tensor-parallel over a ``tp`` mesh of the first ``chips``
devices (the program lays the parameters out by its ``PARTITION_RULES``).
"""

import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from benchmarks.costs import GptShape
from benchmarks.costs import gpt_shape as shape_of  # noqa: F401 - adapter API
from benchmarks.reference import check_outputs  # noqa: F401 - adapter API
from benchmarks.traffic import length_set
from benchmarks.weights import make_weights  # noqa: F401 - adapter API

__all__ = ["shape_of", "make_weights", "Serving", "check_outputs"]


def _engine_model(shape: GptShape, weights: dict, engine_settings: dict,
                  chips: int):
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.models._base import Model, TensorSpec
    from tritonclient_tpu.models.gpt import GptConfig
    from tritonclient_tpu.models.gpt_engine import (GenerationEngine,
                                                    GptEngineModel)

    cfg = GptConfig(
        vocab_size=shape.vocab_size, d_model=shape.d_model,
        n_layers=shape.n_layer, n_heads=shape.n_head, d_ff=shape.d_ff,
        max_len=shape.n_positions,
        layer_norm_eps=shape.layer_norm_epsilon, dtype=jnp.bfloat16,
    )
    mesh = None
    if chips > 1:
        from tritonclient_tpu.parallel import build_mesh

        mesh = build_mesh({"tp": chips}, jax.devices()[:chips])

    class SeededGptEngineModel(GptEngineModel):
        """``GptEngineModel`` with the benchmark's weights. The program's
        constructor draws its own, leaf by leaf (PERF.md, open questions);
        the wire contract below is copied from it unchanged."""

        def __init__(self):
            Model.__init__(self)
            self.cfg = cfg
            self.inputs = [
                TensorSpec("INPUT_IDS", "INT32", [-1, -1]),
                TensorSpec("MAX_TOKENS", "INT32", [1], optional=True),
                TensorSpec("TEMPERATURE", "FP32", [1], optional=True),
                TensorSpec("TOP_K", "INT32", [1], optional=True),
                TensorSpec("SEED", "INT64", [1], optional=True),
            ]
            self.outputs = [TensorSpec("OUTPUT_IDS", "INT32", [-1])]
            self.engine = GenerationEngine(
                cfg, weights, scope_name=self.name, mesh=mesh,
                max_slots=int(engine_settings["max_slots"]),
                block_size=int(engine_settings["block_size"]),
                n_blocks=engine_settings.get("n_blocks"),
                prefill_chunk=int(engine_settings["prefill_chunk"]),
            )

    return SeededGptEngineModel()


def prefill_context_blocks(mix: dict, block_size: int, chunk: int) -> List[int]:
    """The block counts a mix's prefill chunks will ask for: a chunk's
    context is the prompt so far (``gpt_engine.py:_advance_prefills``)."""
    needed = set()
    for prompt in length_set(mix)[0]:
        for start in range(0, prompt, chunk):
            upto = min(start + chunk, prompt)
            needed.add(-(-upto // block_size))
    return sorted(needed)


class Serving:
    """The model, its engine and the gRPC front end, in this process."""

    def __init__(self, shape: GptShape, weights: dict, engine_settings: dict,
                 chips: int = 1):
        from tritonclient_tpu.server import InferenceServer

        self.shape = shape
        self.model = _engine_model(shape, weights, engine_settings, chips)
        self.engine = self.model.engine
        self.model_name = self.model.name
        self._server = InferenceServer(models=[self.model], http=False)
        self._server.start()
        self.address = self._server.grpc_address

    # -- set-up ---------------------------------------------------------------

    def warm(self, mix: dict) -> dict:
        """Compile or load every executable the mix's window will run, and
        no other: the admission scatters, the prefill (lane x context)
        family of the mix's prompt lengths, the slices the engine takes of a
        prefill's result, and decode with its fused widths."""
        import jax

        engine = self.engine
        blocks = prefill_context_blocks(mix, engine.block_size,
                                        engine.prefill_chunk)
        engine.warm_admission()
        engine.warm_prefill(ctx_blocks=blocks)
        self._warm_first_token_slices(min(blocks))
        # One request alone with 8 tokens to make: the prefill gives the
        # first, then 7 are owed: a fused window of 4, one of 2, one step.
        self._drive([self._request(32, 8)])
        # All slots at once: a full bank's decode and its completions.
        self._drive([self._request(32 + 8 * i, 6 + i)
                     for i in range(engine.max_slots)])
        jax.block_until_ready(engine._k)
        return {"prefill_context_blocks": blocks}

    def _warm_first_token_slices(self, context_blocks: int):
        # The engine slices ``firsts[i:i+1]`` off a prefill dispatch's result
        # for each lane that finished: one tiny executable per (lanes, i).
        # Take them off the prefill function's own result, so the array is
        # placed as the window's will be.
        import jax.numpy as jnp

        engine = self.engine
        n_ctx = 1       # the bucket ``warm_prefill`` made for this context
        while n_ctx < context_blocks:
            n_ctx *= 2
        lanes = 1
        while True:
            z = jnp.zeros((lanes,), jnp.int32)
            with engine._cv:
                firsts, engine._k, engine._v = engine._prefill_chunk_fn(
                    engine.params, engine._k, engine._v,
                    jnp.zeros((lanes, engine.prefill_chunk), jnp.int32),
                    jnp.zeros((lanes, n_ctx), jnp.int32),
                    z, jnp.ones((lanes,), jnp.int32), z,
                    jnp.zeros((lanes,), jnp.float32), z,
                )
                for i in range(lanes):
                    firsts[i:i + 1].block_until_ready()
            if lanes >= engine.max_slots:
                return
            lanes = min(lanes * 2, engine.max_slots)

    def _request(self, prompt_len: int, max_tokens: int):
        from benchmarks.traffic import Request

        rng = np.random.default_rng([prompt_len, max_tokens])
        prompt = rng.integers(0, self.shape.vocab_size, (1, prompt_len),
                              dtype=np.int32)
        return Request(-1, prompt, max_tokens)

    def _drive(self, requests: Sequence) -> None:
        """Send ``requests`` together, one stream each, and read them out."""
        from benchmarks.client import open_clients

        clients = open_clients(self.address, self.model_name,
                               self.shape.vocab_size, len(requests))
        errors: List[Optional[str]] = [None] * len(requests)

        def one(i):
            errors[i] = clients[i].send(requests[i]).error

        try:
            threads = [threading.Thread(target=one, args=(i,), daemon=True)
                       for i in range(len(requests))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1200)
        finally:
            for c in clients:
                c.close()
        failed = [e for e in errors if e]
        if failed:
            raise RuntimeError(f"warm-up request failed: {failed[0]}")
        self.wait_idle()

    def wait_idle(self, timeout: float = 30.0) -> None:
        """Until every slot is free and its pages are back in the pool."""
        deadline = time.monotonic() + timeout
        engine = self.engine
        while time.monotonic() < deadline:
            if (all(r is None for r in engine._slot_req)
                    and engine._admit.empty() and engine._pending is None):
                return
            time.sleep(0.005)
        raise RuntimeError("the engine did not become idle")

    # -- what the readers may look at -----------------------------------------

    def pool_usage(self):
        pool = self.engine._pool
        return pool.used_count, pool.n_blocks

    # -- take-down --------------------------------------------------------------

    def close(self) -> None:
        """Stop the front end and the engine, and free the KV pool."""
        self._server.stop()
        engine = self.engine
        engine.shutdown()
        for name in ("_k", "_v"):
            pool = getattr(engine, name, None)
            if pool is not None and not pool.is_deleted():
                pool.delete()
            setattr(engine, name, None)
