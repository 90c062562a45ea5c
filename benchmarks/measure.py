"""Runs of a cell, one process each, and the spread the bounds are set from.

    python3 benchmarks/measure.py --workload <cell> --seeds 11,12,13 \
        [--sets 2] [--seconds <s>] [--trace 0|1] [--out chiprun_out/x.jsonl]

This process never touches JAX (a chip belongs to one process at a time): it
starts BENCHMARK.json's command once per run and keeps each result line.
A spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(command, workload, seed, seconds, trace, timeout=1500):
    argv = command + ["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    began = time.time()
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True,
                          timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    record = {"seed": seed, "trace": trace, "seconds": seconds,
              "rc": proc.returncode, "wall_s": round(time.time() - began, 1),
              "detail": lines[:-1][-12:], "stderr_tail": proc.stderr[-1500:]}
    try:
        record["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        record["result"] = None
        record["stdout_tail"] = proc.stdout[-1500:]
    return record


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=1)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out")
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out_path = args.out and os.path.join(ROOT, args.out)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    sets = []
    for set_index in range(args.sets):
        records = []
        for seed in seeds:
            record = one_run(bench["command"], args.workload, seed, seconds,
                             args.trace)
            record["set"] = set_index
            records.append(record)
            result = record["result"] or {}
            print(f"set {set_index} seed {seed} rc {record['rc']} "
                  f"{record['wall_s']} s correct {result.get('correct')} "
                  + json.dumps({k: v["value"] for k, v in
                                result.get("metrics", {}).items()})
                  + " check " + json.dumps(result.get("check")), flush=True)
            if record["result"] is None:
                print(record["stderr_tail"], flush=True)
            if out_path:
                with open(out_path, "a") as f:
                    f.write(json.dumps(record) + "\n")
        sets.append(records)
    names = sorted({name for records in sets for r in records
                    if r["result"] for name in r["result"]["metrics"]})
    for name in names:
        row = []
        for records in sets:
            values = [r["result"]["metrics"][name]["value"] for r in records
                      if r["result"] and name in r["result"]["metrics"]]
            if len(values) >= 2:
                row.append(f"median {statistics.median(values):.6g} "
                           f"spread {100 * spread(values):.3f}%")
        print(f"{name}: " + " | ".join(row), flush=True)
    bad = [r for records in sets for r in records
           if r["rc"] != 0 or not (r["result"] or {}).get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
