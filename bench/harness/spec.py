"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found from the names in
``BENCHMARK.json``:

* a configuration: the file its ``configs`` entry names (JSON);
* a traffic mix: ``<bench>/traffic/<traffic>.json``, read by the one
  general generator in ``harness.traffic``;
* a per-layer metric: ``<bench>/metrics/<name>.py``, a module with
  ``read(ctx) -> float | None``.

Adding a cell, a mix or a metric therefore adds files and entries and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, NamedTuple

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class Cell(NamedTuple):
    """One workload entry resolved to its files and metric entries."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at ``root``."""
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def resolve(workload: str, *, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve ``workload`` to its configuration, traffic and metrics.

    Raises ``KeyError`` for a name ``BENCHMARK.json`` does not hold and
    ``FileNotFoundError`` for a file that is missing.
    """
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    config["name"] = w["config"]
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    traffic["name"] = w["traffic"]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, *, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read(ctx)`` of ``<bench>/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader at {path}")
    module_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read
