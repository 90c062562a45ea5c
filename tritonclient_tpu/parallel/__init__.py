"""TPU-native parallelism: device meshes, sharding rules, sequence parallelism.

The reference repo has no multi-device code (SURVEY.md §2.5) — its
"distributed backend" is the client↔server wire plane. For the TPU-native
framework, scale-out is first-class: models shard over a
``jax.sharding.Mesh`` (dp/fsdp/tp/sp axes), XLA GSPMD inserts collectives
from `NamedSharding` annotations, and long sequences run either ring
attention (`ppermute` over the sp axis) or Ulysses all-to-all attention,
both inside a partial-manual `jax.shard_map`.
"""

from tritonclient_tpu.parallel.mesh import AXIS_ORDER, auto_mesh, build_mesh
from tritonclient_tpu.parallel.multihost import (
    hybrid_mesh,
    initialize,
    process_local_batch,
)
from tritonclient_tpu.parallel.ring_attention import ring_attention
from tritonclient_tpu.parallel.sharding import (
    named_sharding,
    shard_tree,
    spec_for_path,
    tree_shardings,
)
from tritonclient_tpu.parallel.ulysses import ulysses_attention

__all__ = [
    "AXIS_ORDER",
    "auto_mesh",
    "build_mesh",
    "hybrid_mesh",
    "initialize",
    "named_sharding",
    "process_local_batch",
    "ring_attention",
    "shard_tree",
    "spec_for_path",
    "tree_shardings",
    "ulysses_attention",
]
