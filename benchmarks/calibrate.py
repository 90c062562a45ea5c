"""The two readings an output limit is set from, taken on the chip at the
cell's own size and load, many seeds in one process (set-up is long).

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3,... \
        [--control-seeds 3] [--seconds 8] [--out chiprun_out/calibrate.jsonl]

For each seed: fresh weights from the seed go into the running engine, a
short window at the cell's own load runs, the same sample a benchmark run
would check is drawn, and the widest served-token gap is read (the LOWER
reading is the largest of these). For the first ``--control-seeds`` seeds
the control is read on the same prompts and tokens: the float8 reference in
the program's place (the UPPER reading is the smallest of these).
Not part of a benchmark run, and never run by the driver.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--out")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearsal only: readings from a CPU say "
                             "nothing about the chip's rounding")
    args = parser.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import numpy as np

    from benchmarks import harness, reference, spec
    from benchmarks.client import open_clients
    from benchmarks.traffic import RequestSource

    cell = spec.load_cell(args.workload)
    bench = spec.load_benchmark()
    adapter = harness.load_module(bench, "adapters", cell.config["adapter"],
                                  spec.ROOT)
    loop = harness.load_module(bench, "loops", cell.traffic["loop"], spec.ROOT)
    make_weights, Serving = adapter.make_weights, adapter.Serving
    harness.configure_compile_cache()
    device = harness.find_devices(cell.chips, require_tpu=not args.allow_cpu)
    shape = adapter.shape_of(cell.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    weights = make_weights(seeds[0], shape)
    serving = Serving(shape, weights, cell.config["engine"])
    out_path = args.out and os.path.join(ROOT, args.out)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    rows = []
    try:
        serving.warm(cell.traffic)
        length = reference.pad_length(cell.traffic)
        for n, seed in enumerate(seeds):
            if n:
                serving.wait_idle()
                for leaf in jax.tree.leaves(weights):
                    leaf.delete()
                weights = make_weights(seed, shape)
                serving.engine.params = weights
            clients = open_clients(serving.address, serving.model_name,
                                   shape.vocab_size,
                                   loop.streams(cell.traffic))
            try:
                loop.run(clients, RequestSource(cell.traffic,
                                                shape.vocab_size, seed),
                         args.seconds, cell.traffic)
            finally:
                for c in clients:
                    c.close()
            serving.wait_idle()
            logs = sorted((g for c in clients for g in c.logs),
                          key=lambda g: g.request.index)
            sample = reference.pick_sample(
                logs, int(cell.config["check"]["sample_requests"]), seed)
            samples = [{"prompt": g.request.prompt[0], "tokens": g.tokens}
                       for g in sample]
            began = time.perf_counter()
            gaps = np.concatenate(
                reference.served_gaps(weights, shape, samples, length))
            row = {
                "cell": cell.name, "seed": seed, "device": device,
                "requests": len(logs),
                "failed": sum(g.error is not None for g in logs),
                "checked_tokens": int(gaps.size),
                "program_gap_max": float(gaps.max()),
                "program_gap_p99": float(np.percentile(gaps, 99)),
                "program_gap_mean": float(gaps.mean()),
                "program_tokens_off_best": int((gaps > 0).sum()),
                "reference_s": round(time.perf_counter() - began, 2),
            }
            if n < args.control_seeds:
                control = np.concatenate(reference.served_gaps(
                    weights, shape, samples, length, control=True))
                row.update(control_gap_max=float(control.max()),
                           control_gap_p99=float(np.percentile(control, 99)),
                           control_gap_mean=float(control.mean()),
                           control_tokens_off_best=int((control > 0).sum()))
            rows.append(row)
            print(json.dumps(row), flush=True)
            if out_path:
                with open(out_path, "a") as f:
                    f.write(json.dumps(row) + "\n")
    finally:
        serving.close()
    summary = {"seeds": len(rows)}
    for stat in ("max", "p99", "mean"):
        controls = [r[f"control_gap_{stat}"] for r in rows
                    if f"control_gap_{stat}" in r]
        summary[stat] = {
            "lower_reading": max(r[f"program_gap_{stat}"] for r in rows),
            "upper_reading": min(controls) if controls else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
