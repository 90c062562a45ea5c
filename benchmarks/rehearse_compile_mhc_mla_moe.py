"""Compiles the step programs of a configuration of the MLA / routed-expert
family with a multi-stream residual path for the described (not attached)
v5e, here, without the chip: what the chip's compiler would refuse (memory,
layouts) costs no chip time.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile_mhc_mla_moe.py \
        --config xing4.0-29b-a4b [--layers 2+5] \
        [--programs decode,fused:4,8x128x512,reference:8192]

A program is ``decode``, ``fused:<n>``, ``<lanes>x<chunk>x<context blocks>``
(a prefill bucket), or ``reference:<length>`` / ``control:<length>`` (the
plain reference's pass over one sequence, which has to fit beside the
resident weights: its arguments are the weights alone). ``--layers d+e``
compiles another depth than the file's (the fallbacks of its ``engine_why``).
For each: seconds to compile, ``memory_analysis()`` in GB, how many fusions
the executable holds under the maps' scopes (``mhc_pre`` / ``mhc_post``),
and every ``copy`` / ``dynamic-slice`` / ``dynamic-update-slice`` with the
pool's dimensions (none is right: the pool is the layer scans' carry).
Nothing runs and nothing here is a device number. The pool and the steps
are the program's own (``MlaMoePaged.pool_arrays`` / ``decode_step`` / ...),
so this cannot drift from them.
"""

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--programs", default="decode,fused:4,8x128x512")
    parser.add_argument("--layers", help="dense+expert, e.g. 2+5")
    args = parser.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import spec
    from benchmarks.adapters import mhc_mla_moe_paged_engine as adapter
    from tritonclient_tpu.models import mla_moe

    jax.config.update("jax_enable_compilation_cache", False)
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    shape = adapter.shape_of(config)
    if args.layers:
        dense, expert = (int(n) for n in args.layers.split("+"))
        shape = dataclasses.replace(shape, n_layer=dense + expert,
                                    n_dense_layer=dense)
    cfg = adapter.program_config(shape)
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

    def report(program, compiled, began, **more):
        memory = compiled.memory_analysis()
        print(json.dumps(dict(
            program=program, layers=f"{cfg.n_dense_layers}+{cfg.n_moe_layers}",
            compile_s=round(time.monotonic() - began, 1),
            arguments_gb=round(memory.argument_size_in_bytes / 1e9, 3),
            temporaries_gb=round(memory.temp_size_in_bytes / 1e9, 3),
            **more)), flush=True)

    for program in args.programs.split(","):
        began = time.monotonic()
        if program.split(":")[0] in ("reference", "control"):
            from benchmarks import reference_mhc_mla_moe

            rows = vec(i32, 160)
            report(program, reference_mhc_mla_moe._read.lower(
                params, vec(i32, int(program.split(":")[1])), rows, rows,
                shape, program.startswith("control")).compile(), began)
            continue
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
        # The maps' kernel picks the interpreter off the TPU from the
        # backend's name: compile the chip's own kernel.
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                params, pool, *rest).compile()
        text = compiled.as_text()
        moved = [line.strip()[:120] for line in text.splitlines()
                 if re.match(r"\s*(?:ROOT )?\S+ = \(?\w+\[" + pool_dims
                             + r"\]\S* (copy|copy-start|dynamic-slice|"
                             r"dynamic-update-slice)\(", line)]
        # Fusions and kernels carrying a maps' scope: how many passes the
        # compiler made of a layer's two pre and two post (both scans' bodies).
        maps = [line for line in text.splitlines()
                if re.search(r" (fusion|custom-call)\(", line)
                and re.search(r"mhc_(pre|post)", line)]
        report(program, compiled, began, name=fn.__name__,
               maps_fusions=len(maps), pool_shaped_moves=moved)
    return 0


if __name__ == "__main__":
    sys.exit(main())
