"""Read a cell's checks over many seeds in one process (how limits are set).

    python bench/readings.py --workload pamap-p1.ingest --seeds 11,12,13 \
        --seconds 5 [--control 1] [--cpu 1]

Each seed is one ``run_cell`` of the cell at its own sizes, with a
``--seconds`` window; one JSON line per seed gives every number compared
and its limit, and the run's end-to-end metrics.  ``--control 1`` reads
the control in the program's place.  The benchmark's own runs do not use
this script.  Like ``run.py`` it refuses a machine without a TPU, unless
``--cpu 1`` asks for the CPU witness (XLA paths, float32).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--cpu", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from harness import runner, spec
    from repro.compile_cache import enable_compile_cache

    cell = spec.resolve(args.workload)
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if (devices[0].platform != "tpu") != bool(args.cpu) or len(devices) < cell.chips:
        print(f"readings: {cell.name} needs {cell.chips} {'CPU' if args.cpu else 'TPU'} "
              f"devices, JAX found {len(devices)} {devices[0].platform}", file=sys.stderr)
        return 3
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = runner.run_cell(cell, devices, seed=seed, seconds=args.seconds, trace=False,
                            t_start=t_start, control=bool(args.control))
        print(json.dumps({"workload": cell.name, "seed": seed, "control": args.control,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                          "checks": r["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
