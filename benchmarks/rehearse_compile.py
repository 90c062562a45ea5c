"""Rehearsal 3 of the on-chip-measurement guide, run here without the chip:
compile the engine's chunked prefill at a configuration's real shapes for the
described v5e and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py <config.json> \
        prefill:8x64 prefill:1x2

``prefill:<lanes>x<context pages>``: the (lane, context) buckets a mix's
longest prompts reach are the largest programs of a cell. Nothing runs, so
this gives no time; a program that does not fit 16 GB is refused here, at no
chip time. Not part of a benchmark run.
"""

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.costs import gpt_shape
    from benchmarks.weights import make_weights
    from tritonclient_tpu.models import gpt_engine
    from tritonclient_tpu.models.gpt import GptConfig

    jax.config.update("jax_enable_compilation_cache", False)
    with open(sys.argv[1]) as f:
        config = json.load(f)
    s = gpt_shape(config)
    engine = config["engine"]
    slots, bs, chunk = (int(engine["max_slots"]), int(engine["block_size"]),
                        int(engine["prefill_chunk"]))
    max_blocks = s.n_positions // bs
    n_blocks = int(engine.get("n_blocks") or 1 + slots * max_blocks)
    cfg = GptConfig(vocab_size=s.vocab_size, d_model=s.d_model,
                    n_layers=s.n_layer, n_heads=s.n_head, d_ff=s.d_ff,
                    max_len=s.n_positions,
                    layer_norm_eps=s.layer_norm_epsilon, dtype=jnp.bfloat16)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                          jax.eval_shape(lambda: make_weights(0, s)))
    pool = on_chip((s.n_layer, n_blocks, bs, s.n_head, s.head_dim),
                   jnp.bfloat16)
    i32 = lambda *shape: on_chip(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: on_chip(shape, jnp.float32)  # noqa: E731
    for what in sys.argv[2:]:
        kind, _, size = what.partition(":")
        began = time.time()
        if kind == "prefill":
            lanes, pages = (int(x) for x in size.split("x"))
            fn = jax.jit(functools.partial(
                gpt_engine._prefill_chunk_paged, cfg=cfg, block_size=bs,
                proj_fn=None), donate_argnums=(1, 2))
            lowered = fn.lower(params, pool, pool, i32(lanes, chunk),
                               i32(lanes, pages), i32(lanes), i32(lanes),
                               i32(lanes), f32(lanes), i32(lanes))
        else:
            raise SystemExit(f"{what}: only prefill:<lanes>x<pages> is "
                             "rehearsed here")
        m = lowered.compile().memory_analysis()
        print(json.dumps({
            "config": config["name"], "program": what,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "compile_s": round(time.time() - began, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
