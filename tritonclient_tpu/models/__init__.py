"""JAX/Flax model zoo served by the in-process backend.

- ``simple`` family: behavioral parity with the Triton qa models the
  reference examples drive (add/sub, string, stateful sequence, decoupled
  repeat).
- ``resnet`` / ``bert``: the benchmark models (BASELINE.md targets), built
  TPU-first in Flax with mesh-sharded variants in tritonclient_tpu.parallel.
- ``gpt``: causal decoder with KV-cache generation served as a decoupled
  token stream — the genai-perf target (tritonclient_tpu.genai_perf).
- ``gpt_engine``: the paged continuous-batching engine; it serves any
  family that is a ``PagedModel``: the GPT block, and ``mla_moe`` (latent
  attention over a latent page pool, rotary positions, routed experts with
  a shared one).
"""

from tritonclient_tpu.models._base import Model, TensorSpec  # noqa: F401
from tritonclient_tpu.models.simple import (  # noqa: F401
    RepeatModel,
    SimpleModel,
    SimpleSequenceModel,
    SimpleStringModel,
)
