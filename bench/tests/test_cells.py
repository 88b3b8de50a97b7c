"""Each cell rehearsed on the CPU at a tiny size, its control, and faults.

The rehearsals call ``run_cell`` directly (Pallas kernels interpreted).
A sound run must come out correct.  The control (the bfloat16 reference
in the program's place) and each planted fault of the timed path must
come out not correct.  The four-site cell runs in a child process on
four virtual CPU devices.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH, ROOT, rehearse
from harness import spec

import repro.core.distributed as dist
from repro.query.engine import QueryEngine
from repro.query.store import SketchStore
from repro.runtime.registry import ShardProtocol

CELLS = spec.load_benchmark()["workloads"]
ONE_CHIP = [w["name"] for w in CELLS if w["chips"] == 1]
FOUR_CHIPS = [w["name"] for w in CELLS if w["chips"] == 4]
CHECKS = ["unpublished", "publish_gap", "mass_gap", "sketch_err", "state_err",
          "answer_err", "answer_gap", "spectrum_gap"]


def _failed(result: dict) -> set:
    return {k for k, c in result["checks"].items() if not c["value"] <= c["limit"]}


def _fresh_runners(monkeypatch):
    monkeypatch.setattr(dist, "_RUNNER_CACHE", {})


def _state_unchanged(monkeypatch):
    _fresh_runners(monkeypatch)
    monkeypatch.setitem(dist._STEPS, "P1", lambda cfg, st, rows: st)


def _half_batch_left_out(monkeypatch):
    """Half of each batch dropped, the rest scaled so the mass is kept on
    average: the mean taken over the rest."""
    real = ShardProtocol.step

    def half(self, rows, sites=None):
        rows = np.array(rows, np.float32)
        keep = rows.shape[0] // 2
        rows[:keep] *= np.sqrt(2.0)
        rows[keep:] = 0.0
        return real(self, rows, sites)

    monkeypatch.setattr(ShardProtocol, "step", half)


def _published_sketch_altered(monkeypatch):
    real = SketchStore.publish

    def altered(self, tenant, matrix, **kw):
        return real(self, tenant, np.asarray(matrix) + np.float32(1e-3), **kw)

    monkeypatch.setattr(SketchStore, "publish", altered)


def _answer_altered(monkeypatch):
    """Each served answer is the one of the next direction."""
    real = QueryEngine._pallas_batch
    monkeypatch.setattr(QueryEngine, "_pallas_batch",
                        lambda self, snap, x: np.roll(real(self, snap, x), 1))


FAULTS = {
    "state_unchanged": (_state_unchanged, "mass_gap"),
    "half_batch_left_out": (_half_batch_left_out, "mass_gap"),
    "published_sketch_altered": (_published_sketch_altered, "publish_gap"),
    "answer_altered": (_answer_altered, "answer_gap"),
}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_cell_rehearsal(cell):
    r = rehearse(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ingest_rows_per_s", "publish_lag_p95_ms", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1
    assert list(r["checks"]) == CHECKS and list(r)[-1] == "checks"


def test_traced_rehearsal_reads_program_counters():
    r = rehearse(ONE_CHIP[0], trace=True)
    assert r["correct"], r["checks"]
    # the CPU trace holds no TPU plane: only counter-read metrics appear
    assert set(r["metrics"]) == {"publish_host_ms"}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_fails(cell):
    r = rehearse(cell, control=True)
    assert not r["correct"]
    assert "mass_gap" in _failed(r)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_fails(cell, fault, monkeypatch):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    r = rehearse(cell)
    assert not r["correct"]
    assert caught_by in _failed(r), r["checks"]


FOUR_SITES = """
import json, sys
sys.path[:0] = {paths!r}
import numpy as np
import pytest
import conftest
import repro.core.distributed as dist
from test_cells import FAULTS

class Shim:  # lax, with the exchange between chips left out
    def __getattr__(self, name):
        return getattr(dist.jax.lax, name)
    @staticmethod
    def all_gather(x, axis):
        return x[None]

def exchange_left_out(mp):
    mp.setattr(dist, "_RUNNER_CACHE", {{}})
    mp.setattr(dist, "lax", Shim())

faults = dict(FAULTS, exchange_left_out=(exchange_left_out, "state_err"))
out = {{"sound": conftest.rehearse({cell!r}),
        "control": conftest.rehearse({cell!r}, control=True)}}
for name, (plant, _) in faults.items():
    with pytest.MonkeyPatch.context() as mp:
        plant(mp)
        out[name] = conftest.rehearse({cell!r})
print(json.dumps({{k: {{"correct": r["correct"], "checks": r["checks"],
                       "count": r["device"]["count"]}} for k, r in out.items()}}))
"""


@pytest.mark.parametrize("cell", FOUR_CHIPS)
def test_four_site_cell_rehearsal(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = FOUR_SITES.format(paths=[str(ROOT / "src"), str(BENCH), str(BENCH / "tests")],
                               cell=cell)
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          cwd=BENCH / "tests", env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["count"] == 4
    assert not out["control"]["correct"]
    caught = dict({k: v[1] for k, v in FAULTS.items()}, exchange_left_out="state_err")
    for fault, check in caught.items():
        r = out[fault]
        assert not r["correct"], fault
        assert not r["checks"][check]["value"] <= r["checks"][check]["limit"], (fault, r)


def _command(cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", "--workload", ONE_CHIP[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          capture_output=True, text=True, cwd=cwd, env=env, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _command(ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _command(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
