"""Reads BENCHMARK.json and the data files a cell names.

A cell names a configuration and a traffic mix; each lives in a file of its
own that this module finds by the name in BENCHMARK.json, so a later PR adds
a cell by adding files and entries and edits none that exist.
"""

import json
import os
from dataclasses import dataclass
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(ValueError):
    """BENCHMARK.json or a data file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{path} does not exist") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config: dict            # the configuration's file, as run
    traffic: dict           # the traffic mix's file
    end_to_end: List[dict]  # metric entries this cell reports
    per_layer: List[dict]
    run_seconds: int


def load_benchmark(path: Optional[str] = None) -> dict:
    return _load_json(path or BENCHMARK_FILE)


def _reported_in(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def _under_paths(bench: dict, root: str, *parts: str) -> str:
    """The file ``<path>/<parts...>`` under the first of ``paths`` that has it."""
    for base in bench["paths"]:
        candidate = os.path.join(root, base, *parts)
        if os.path.exists(candidate):
            return candidate
    raise SpecError(
        f"no {'/'.join(parts)} under any of {bench['paths']}")


def traffic_file(bench: dict, mix: str, root: str) -> str:
    """``<path>/traffic/<mix>.json``."""
    return _under_paths(bench, root, "traffic", mix + ".json")


def reader_file(bench: dict, kind: str, metric: str, root: str) -> str:
    """``<path>/<kind>/<metric>.py``: one reader, one file, found by name."""
    return _under_paths(bench, root, kind, metric + ".py")


def load_cell(workload: str, path: Optional[str] = None,
              root: Optional[str] = None) -> Cell:
    bench = load_benchmark(path)
    root = root or ROOT
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(
            f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SpecError(f"{workload} names no configuration in `configs`")
    config = _load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _load_json(traffic_file(bench, entry["traffic"], root))
    cell = Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_in(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_in(m, workload) and any(
                       e["name"] == m["moves"] and _reported_in(e, workload)
                       for e in bench["end_to_end"])],
        run_seconds=int(bench["run_seconds"]),
    )
    for kind, metrics in (("end_to_end", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in metrics:
            reader_file(bench, kind, m["name"], root)   # fail before set-up
    return cell
