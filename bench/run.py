"""Run one cell of the chip benchmark once and print its result line.

    python bench/run.py --workload pamap-p1.ingest --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` at the root of the checkout.  The run
refuses to start where JAX finds no TPU, or fewer chips than the cell
asks for.  It sets up (streams from ``--seed``, the system, every shape
the window uses), measures for ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON object as the
last line of standard output: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics from a profiler trace of the window
with ``--trace 1``.  Each number the check compared is printed beside its
limit, last on standard error and under ``checks``, the last key of that
line.

``--control 1`` puts the bfloat16 reference in the program's place in the
check, which must then fail; ``--keep-trace PATH`` also writes the traced
window's events (how ``bench/tests/data`` was recorded).  The benchmark's
own runs use neither.
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
# The program and the harness; the compile cache sits inside the checkout
# at a fixed path, and the program's own cache helper is handed it.
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu logs under /tmp unless told otherwise; a run writes only inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default=None,
                        help="also write the trace's neutral events (gzipped JSON) here")
    args = parser.parse_args(argv)

    from harness import runner, spec
    from repro.compile_cache import enable_compile_cache

    cell = spec.resolve(args.workload)
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 3
    result = runner.run_cell(cell, devices, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             control=bool(args.control), keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
