"""A configuration, a traffic mix and a metric are found by name from files."""
from __future__ import annotations

import json
import shutil

from conftest import BENCH, ROOT
from harness import spec


def test_new_files_are_found_by_name_without_an_edit(tmp_path):
    bench = tmp_path / "bench"
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / kind, bench / kind)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    # what a later PR adds: one file of each kind, and entries naming them
    config = json.loads((BENCH / "configs" / "msd-p1.json").read_text())
    (bench / "configs" / "msd-p1-eps05.json").write_text(json.dumps({**config, "eps": 0.05}))
    (bench / "traffic" / "ingest-b1000.json").write_text(json.dumps(
        {"loop": "closed", "rows_per_site": 1000, "warm_steps": 2}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx == 'ctx' else None\n")
    doc["configs"].append({"name": "msd-p1-eps05", "source": "https://arxiv.org/abs/1404.7571",
                           "file": "bench/configs/msd-p1-eps05.json", "reduced": ["m"],
                           "why": "test"})
    doc["workloads"].append({"name": "msd-p1-eps05.ingest", "config": "msd-p1-eps05",
                             "traffic": "ingest-b1000", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                             "source": "program_counter", "layer": "device",
                             "moves": "ingest_rows_per_s", "workloads": ["msd-p1-eps05.ingest"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.resolve("msd-p1-eps05.ingest", root=tmp_path, bench_dir=bench)
    assert cell.config["eps"] == 0.05 and cell.config["name"] == "msd-p1-eps05"
    assert cell.traffic == {"loop": "closed", "rows_per_site": 1000, "warm_steps": 2,
                            "name": "ingest-b1000"}
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert spec.metric_reader("new_metric", bench_dir=bench)("ctx") == 42.0
    # the cells already there resolve as before, without the new metric
    old = spec.resolve("msd-p1.ingest", root=tmp_path, bench_dir=bench)
    assert old.chips == 1 and "new_metric" not in {m["name"] for m in old.per_layer}


def test_every_cell_of_the_benchmark_resolves():
    doc = spec.load_benchmark()
    for w in doc["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == cell.config["chips"] == cell.config["m"], w["name"]
        assert set(cell.config["limits"]) == {"mass_gap", "answer_gap", "spectrum_gap"}
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
