"""The test-only tiny cell of the MLA / routed-expert family, added the way
``_bench_tiny.py`` adds the GPT one: a configuration file and entries, the
``tiny-chat`` mix that is there, no edit to the harness. Its BENCHMARK file
is made from the real one; the per-layer metrics keep their ``workloads``
lists with this cell in the real cell's place, so the GPT family's cost
readers are not asked about a shape that is not theirs."""

import json
import os

from _bench_tiny import REPO

CELL = "tiny-mla-moe.tiny-chat"
REAL_CELL = "joyai-llm-flash.chat_half"


def tiny_benchmark_file(directory) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["run_seconds"] = 2
    bench["configs"] = [{
        "name": "tiny-mla-moe", "source": "test only",
        "file": "tests/benchmark/configs/tiny-mla-moe.json",
        "reduced": [], "why": "test only"}]
    bench["workloads"] = [{
        "name": CELL, "config": "tiny-mla-moe", "traffic": "tiny-chat",
        "chips": 1, "why": "test only"}]
    for metric in bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL if w == REAL_CELL else w
                                   for w in metric["workloads"]]
    path = os.path.join(str(directory), "BENCHMARK.tiny-mla-moe.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
