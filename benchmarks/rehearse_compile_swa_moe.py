"""Compiles a window/global routed configuration's step programs for the
described (not attached) v5e, here, without the chip: what the chip's
compiler would refuse (memory, layouts, the kernel's scalar memory) costs
no chip time.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile_swa_moe.py \
        --config k-exaone-236b-a23b [--programs decode,fused:4,8x256] \
        [--prefill-chunk 512]

A program is ``decode``, ``fused:<n>``, ``<lanes>x<chunk>`` (a prefill
lane bucket: the family's prefill always takes the whole table), or
``reference:<length>`` / ``control:<length>`` (the plain reference's pass
over one sequence, which has to fit beside the resident weights once the
engine's pools are freed: its arguments are the weights alone). For each:
seconds to compile, ``memory_analysis()`` in GB, and every ``copy`` /
``dynamic-slice`` / ``dynamic-update-slice`` with a pool's dimensions (none
is right: the pools are the layer scans' carry). Nothing runs and nothing
here is a device number. The pools and the steps are the program's own
(``SwaMoePaged.pool_arrays`` / ``decode_step`` / ...), so this cannot drift
from them.
"""

import argparse
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
    parser.add_argument("--programs", default="decode,fused:4,8x256")
    parser.add_argument("--prefill-chunk", type=int,
                        help="the ring's chunk, if not the configuration's")
    args = parser.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import spec
    from benchmarks.adapters import swa_moe_paged_engine as adapter
    from tritonclient_tpu.models import swa_moe

    jax.config.update("jax_enable_compilation_cache", False)
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    cfg = adapter.program_config(adapter.shape_of(config))
    engine = config["engine"]
    slots, bs = int(engine["max_slots"]), int(engine["block_size"])
    chunk = args.prefill_chunk or int(engine["prefill_chunk"])
    model = swa_moe.SwaMoePaged(cfg, slots, chunk)
    width = model.ring_pages(bs) + cfg.max_len // bs
    n_blocks = engine.get("n_blocks") or 1 + slots * (cfg.max_len // bs)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: swa_moe.init_params(jax.random.PRNGKey(0), cfg)))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.pool_arrays(n_blocks, bs)))
    pool_dims = ",".join(map(str, pools[0].shape))
    i32, f32 = jnp.int32, jnp.float32

    def bank(n):
        return (vec(i32, n, width),) + (vec(i32, n),) * 4 + (
            vec(f32, n), vec(i32, n))

    for program in args.programs.split(","):
        if program.split(":")[0] in ("reference", "control"):
            from benchmarks import reference_swa_moe

            length = int(program.split(":")[1])
            rows = vec(i32, 512)
            began = time.monotonic()
            compiled = reference_swa_moe._read.lower(
                params, vec(i32, length), rows, rows, adapter.shape_of(config),
                program.startswith("control")).compile()
            memory = compiled.memory_analysis()
            print(json.dumps({
                "program": program,
                "compile_s": round(time.monotonic() - began, 1),
                "arguments_gb": round(memory.argument_size_in_bytes / 1e9, 3),
                "temporaries_gb": round(memory.temp_size_in_bytes / 1e9, 3)}),
                flush=True)
            continue
        if program == "decode":
            fn, rest = model.decode_step(bs), bank(slots)
        elif program.startswith("fused:"):
            fn = model.decode_fused(bs, int(program[6:]))
            rest = bank(slots)
        else:
            lanes, rows = (int(n) for n in program.split("x"))
            fn = model.prefill_chunk(bs)
            rest = (vec(i32, lanes, rows), vec(i32, lanes, width)) + (
                vec(i32, lanes),) * 3 + (vec(f32, lanes), vec(i32, lanes))
        began = time.monotonic()
        # The kernel picks the interpreter off the TPU from the backend's
        # name: compile the chip's own kernel.
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
                params, *pools, *rest).compile()
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
