"""The test-only tiny cell of the MLA / routed-expert family with a
multi-stream residual path, added the way ``_bench_tiny_mla_moe.py`` adds
its family's: a configuration file and entries, the ``tiny-chat`` mix that
is there, no edit to the harness. Its BENCHMARK file is made from the real
one; the per-layer metrics keep their ``workloads`` lists with this cell in
the real cell's place, so another family's cost readers are not asked about
a shape that is not theirs."""

import json
import os

from _bench_tiny import REPO

CELL = "tiny-mhc-mla-moe.tiny-chat"
REAL_CELL = "xing4.0-29b-a4b.code"


def tiny_benchmark_file(directory) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["run_seconds"] = 2
    bench["configs"] = [{
        "name": "tiny-mhc-mla-moe", "source": "test only",
        "file": "tests/benchmark/configs/tiny-mhc-mla-moe.json",
        "reduced": [], "why": "test only"}]
    bench["workloads"] = [{
        "name": CELL, "config": "tiny-mhc-mla-moe", "traffic": "tiny-chat",
        "chips": 1, "why": "test only"}]
    for metric in bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL if w == REAL_CELL else w
                                   for w in metric["workloads"]]
    path = os.path.join(str(directory), "BENCHMARK.tiny-mhc-mla-moe.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
