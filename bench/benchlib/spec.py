"""Where the benchmark's data lives, and how a cell's pieces are found.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

    bench/configs/<config>.json     sizes, deployment, source, cuts
    bench/traffic/<traffic>.json    parameters for the one generator
    bench/metrics/<metric>.py       a reader: ``read(run) -> float | None``
    bench/reference/<name>.py       a plain reference, named by a config

Adding a cell therefore means adding files and a ``workloads`` entry;
no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]     # bench/
ROOT = BENCH.parent                             # the checkout


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: Optional[Dict[str, Any]] = None
             ) -> Dict[str, Any]:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def load_module(path: Path):
    """Import a file by path (names may hold dots, so no package
    import)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str):
    return load_module(BENCH / "reference" / f"{name}.py")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def metrics_for(cell: str, kind: str,
                bench: Optional[Dict[str, Any]] = None) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports. A
    metric with a ``workloads`` list names its cells. Without one, an
    end-to-end metric applies to every cell, and a per-layer metric to
    every cell that reports the end-to-end metric it ``moves``."""
    bench = bench or benchmark()
    e2e = {m["name"]: m for m in metrics_for_e2e(cell, bench)}
    if kind == "end_to_end":
        return list(e2e.values())
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metrics_for_e2e(cell: str, bench: Dict[str, Any]) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]
