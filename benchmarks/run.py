"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  BENCH_SCALE env var scales
stream sizes toward the paper's full 1e7-element runs (default 1.0 keeps
the whole suite to a few minutes on one CPU core).

``--smoke`` shrinks every stream via ``BENCH_SCALE=0.25`` and runs only
the modules CI gates on (kernels, runtime pipeline, cluster scaling) —
a couple of minutes that still exercises every launch path end to end,
including the packed-ingest shootouts, without blessing their numbers.
An optional positional substring still filters module names.
"""
from __future__ import annotations

import os
import sys

SMOKE_MODULES = (
    "kernels_bench",
    "runtime_pipeline",
    "cluster_scaling",
    "windowed_tracking",
)

# BENCH_*.json files whose "obs" telemetry snapshot the smoke lane
# verifies, and the headline counters that must be nonzero in each.
SMOKE_OBS_FILES = (
    "BENCH_runtime_pipeline.json",
    "BENCH_cluster_scaling.json",
    "BENCH_windowed_tracking.json",
)
SMOKE_OBS_HEADLINE = (
    "repro_ingest_rows_total",
    "repro_engine_packed_launches_total",
)


def check_obs_snapshots() -> None:
    """Assert each smoke BENCH json carries a parseable, nonempty
    telemetry snapshot: it must round-trip through
    ``MetricsRegistry.from_snapshot`` and its headline counters must
    have actually counted something."""
    import json

    from repro.obs import MetricsRegistry

    for name in SMOKE_OBS_FILES:
        path = os.path.join(os.getcwd(), name)
        with open(path) as f:
            doc = json.load(f)
        reg = MetricsRegistry.from_snapshot(doc["obs"])
        for family in SMOKE_OBS_HEADLINE:
            total = sum(s.value for _, s in reg.get(family).series())
            assert total > 0, f"{name}: headline counter {family} is zero"
        print(f"# obs snapshot ok: {name}", flush=True)


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    if smoke:
        args = [a for a in args if a != "--smoke"]
        os.environ.setdefault("BENCH_SCALE", "0.25")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        cluster_scaling,
        grad_compression,
        hh_protocols,
        kernels_bench,
        leverage_protocols,
        matrix_protocols,
        p4_negative,
        quantile_protocols,
        query_service,
        roofline_table,
        runtime_pipeline,
        tradeoff,
        windowed_tracking,
    )

    print("name,us_per_call,derived")
    only = args[0] if args else None
    for mod in (
        hh_protocols,
        quantile_protocols,
        leverage_protocols,
        matrix_protocols,
        tradeoff,
        p4_negative,
        grad_compression,
        kernels_bench,
        query_service,
        runtime_pipeline,
        windowed_tracking,
        cluster_scaling,
        roofline_table,
    ):
        name = mod.__name__.split(".")[-1]
        if smoke and name not in SMOKE_MODULES:
            continue
        if only and only not in name:
            continue
        mod.run()

    if smoke and not only:
        check_obs_snapshots()


if __name__ == "__main__":
    main()
