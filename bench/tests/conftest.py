"""Benchmark tests run on the CPU at tiny sizes: ``python -m pytest bench/tests``.

They rehearse each cell through ``run_cell`` (the command itself refuses a
machine without a TPU; Pallas kernels run interpreted here), check the
trace reducer on a recorded trace and the roofline counts by hand, and
show that the check fails under the control and under planted faults.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

TINY_BLOCK = 400  # rows per site per super-step in a rehearsal
TINY_ROWS = 12_000  # rows of a rehearsal's stream


def tiny(name: str):
    """Cell ``name`` cut to a CPU test: a 12,000-row stream in 400-row
    blocks per site; widths, eps, protocol and sites as configured."""
    from harness import spec

    cell = spec.resolve(name)
    config = dict(cell.config, stream=dict(cell.config["stream"], rows=TINY_ROWS))
    traffic = dict(cell.traffic, rows_per_site=TINY_BLOCK)
    return cell._replace(config=config, traffic=traffic)


def rehearse(name: str, *, seed: int = 2**31 + 11, seconds: float = 1.0, **kw) -> dict:
    """One tiny run of cell ``name`` on this process's CPU devices."""
    import jax
    from harness import runner

    cell = tiny(name)
    return runner.run_cell(cell, jax.devices()[: cell.chips], seed=seed, seconds=seconds,
                           trace=kw.pop("trace", False), t_start=time.perf_counter(), **kw)
