"""The test-only tiny cell: added the way any later cell is, as a
configuration file, a traffic file and entries, with no edit to the harness.
Its BENCHMARK file is made from the real one (same metrics, same readers)."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tiny-test.tiny-chat"
OPEN_CELL = "tiny-test.tiny-open"


def tiny_benchmark_file(directory) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["run_seconds"] = 2
    bench["configs"] = [{
        "name": "tiny-test", "source": "test only",
        "file": "tests/benchmark/configs/tiny-test.json",
        "reduced": [], "why": "test only"}]
    bench["workloads"] = [{
        "name": CELL, "config": "tiny-test", "traffic": "tiny-chat",
        "chips": 1, "why": "test only"}, {
        "name": OPEN_CELL, "config": "tiny-test", "traffic": "tiny-open",
        "chips": 1, "why": "test only"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)
    path = os.path.join(str(directory), "BENCHMARK.tiny.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
