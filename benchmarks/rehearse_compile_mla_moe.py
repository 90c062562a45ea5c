"""Compiles an MLA / routed-expert configuration's step programs for the
described (not attached) v5e, here, without the chip: what the chip's
compiler would refuse (memory, layouts) costs no chip time.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile_mla_moe.py \
        --config joyai-llm-flash [--programs decode,fused:4,8x256x128]

A program is ``decode``, ``fused:<n>`` or ``<lanes>x<chunk>x<context
blocks>`` (a prefill bucket). For each: seconds to compile,
``memory_analysis()`` in GB, and every ``copy`` / ``dynamic-slice`` /
``dynamic-update-slice`` with the pool's dimensions (none is right: the
pool is the layer scans' carry). Nothing runs and nothing here is a device
number. The pool and the steps are the program's own
(``MlaMoePaged.pool_arrays`` / ``decode_step`` / ...), so this cannot drift
from them.
"""

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--programs", default="decode,fused:4,8x256x128")
    args = parser.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import spec
    from benchmarks.adapters import mla_moe_paged_engine as adapter
    from tritonclient_tpu.models import mla_moe

    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    cfg = adapter.program_config(adapter.shape_of(config))
    engine = config["engine"]
    slots, bs = int(engine["max_slots"]), int(engine["block_size"])
    width = cfg.max_len // bs
    n_blocks = engine.get("n_blocks") or 1 + slots * width
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    model = mla_moe.MlaMoePaged(cfg)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    (pool,) = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.pool_arrays(n_blocks, bs)))
    pool_dims = ",".join(map(str, pool.shape))
    i32, f32 = jnp.int32, jnp.float32

    def bank(n):
        return (vec(i32, n, width),) + (vec(i32, n),) * 4 + (
            vec(f32, n), vec(i32, n))

    for program in args.programs.split(","):
        if program == "decode":
            fn, rest = model.decode_step(bs), bank(slots)
        elif program.startswith("fused:"):
            fn = model.decode_fused(bs, int(program[6:]))
            rest = bank(slots)
        else:
            lanes, chunk, ctx = (int(n) for n in program.split("x"))
            fn = model.prefill_chunk(bs)
            rest = (vec(i32, lanes, chunk), vec(i32, lanes, ctx)) + (
                vec(i32, lanes),) * 3 + (vec(f32, lanes), vec(i32, lanes))
        began = time.monotonic()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pool, *rest).compile()
        memory = compiled.memory_analysis()
        moved = [line.strip()[:120] for line in compiled.as_text().splitlines()
                 if re.match(r"\s*(?:ROOT )?\S+ = \(?\w+\[" + pool_dims
                             + r"\]\S* (copy|copy-start|dynamic-slice|"
                             r"dynamic-update-slice)\(", line)]
        print(json.dumps({
            "program": program, "name": fn.__name__,
            "compile_s": round(time.monotonic() - began, 1),
            "arguments_gb": round(memory.argument_size_in_bytes / 1e9, 3),
            "temporaries_gb": round(memory.temp_size_in_bytes / 1e9, 3),
            "pool_shaped_moves": moved}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
