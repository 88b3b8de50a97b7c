"""One run of one cell: set-up, the measured window, the check, the result.

``run_cell`` takes devices that the caller has already checked; the
command (``bench/run.py``) refuses to start without the chips a cell
needs, so CPU rehearsals call ``run_cell`` directly.
"""
from __future__ import annotations

import contextlib
import glob
import json
import math
import shutil
import sys
import time
from typing import NamedTuple

import numpy as np

from . import checks, roofline, spec
from .compile_meter import CompileMeter
from .system import System
from .trace import load_xplane, reduce, save_neutral
from .traffic import Stream

TRACE_DIR = spec.ROOT / ".bench_trace"
DIRECTIONS, TOP_DIRECTIONS = 256, 8  # served answers checked after the window

clock = time.perf_counter


class Ctx(NamedTuple):
    """What a per-layer metric reader may read."""

    config: dict
    device_kind: str
    sketch_rows: int
    trace: object  # harness.trace.Reduced, or None
    start: dict  # program registry snapshot at window open
    end: dict  # ... and at window close
    roofline: object = roofline

    def _series(self, snap: dict, name: str) -> list:
        return snap["metrics"].get(name, {}).get("series", [])

    def counter(self, name: str) -> float:
        """Growth of a program counter (summed over its series) in the window."""
        return (sum(s["value"] for s in self._series(self.end, name))
                - sum(s["value"] for s in self._series(self.start, name)))


class Window(NamedTuple):
    seconds: float  # from window open to the last super-step's return
    steps: int  # super-steps handed in
    unpublished: int  # super-steps no servable snapshot held on return
    lags_s: np.ndarray  # per super-step: hand-off to servable


def _spans(enabled: bool):
    """``span(name)``: a profiler annotation when tracing, else nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def _closed_loop(system: System, stream: Stream, first: int, seconds: float,
                 span) -> Window:
    """Super-steps back to back until ``seconds`` have passed.  A step is
    servable when ``ingest_many`` returns having published and refreshed
    one snapshot that holds every row handed in so far."""
    lags, unpublished, step = [], 0, first
    t0 = clock()
    while clock() - t0 < seconds:
        rows = stream.batch(step)
        handed = clock()
        with span("bench.ingest_many"):
            published = system.ingest(rows)
        lags.append(clock() - handed)
        step += 1
        if published != 1 or system.snapshot().n_seen != step * stream.rows_per_step:
            unpublished += 1
    return Window(clock() - t0, step - first, unpublished, np.asarray(lags))


def _p95_ms(values_s: np.ndarray) -> float:
    return float(np.percentile(values_s, 95, method="higher")) * 1e3


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def _log(**fields) -> None:
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, devices, *, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False, keep_trace: str | None = None) -> dict:
    """Run ``cell`` once on ``devices``; returns the result line's object."""
    import jax

    config, traffic = cell.config, cell.traffic
    if traffic.get("loop") != "closed":
        raise ValueError(f"traffic {traffic['name']!r}: unknown loop {traffic.get('loop')!r}")
    devices = list(devices)[: cell.chips]
    span = _spans(trace)

    # -- set-up: the stream, the system, every program the window runs -------
    stream = Stream(config, traffic, seed)
    system = System(config, devices)
    warm = int(traffic["warm_steps"])
    with CompileMeter() as meter:
        for k in range(warm):
            system.ingest(stream.batch(k))
        setup_compiles = meter.compiles
        setup_s = clock() - t_start

        # -- the measured window ----------------------------------------------
        start = system.counters()
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # TraceMe spans only: no per-call events
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        with span("bench.window"):
            win = _closed_loop(system, stream, warm, seconds, span)
        if trace:
            jax.profiler.stop_trace()
        window_compiles = meter.compiles - setup_compiles
    end = system.counters()
    memory_peak = _memory_peak(devices)
    steps = warm + win.steps
    _log(setup_s=setup_s, setup_compiles=setup_compiles, window_compiles=window_compiles,
         cache_hits=meter.cache_hits, cache_misses=meter.cache_misses,
         steps=win.steps, window_s=win.seconds)

    # -- what the window produced, read back; then the reference --------------
    ref = checks.Reference(stream, steps)
    x = ref.directions(seed, DIRECTIONS, TOP_DIRECTIONS)
    answers, version = system.serve(x)
    from_spectrum, refreshed = system.serve_from_spectrum(x, version)
    newest = system.snapshot()
    served = checks.Served(x, answers, system.snapshot(version).matrix,
                           from_spectrum, refreshed and version == newest.version)
    state = system.state()
    sketch_rows = newest.matrix.shape[0]
    system.close()
    found = checks.compare(
        ref, config, state=state, newest=newest.matrix, served=served,
        unpublished=win.unpublished,
        control=checks.Control(stream, steps, ref.gram) if control else None)

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": all(c.ok for c in found), "attempted": int(win.steps),
              "failed": int(win.unpublished)}
    if not trace:
        e2e = {
            "ingest_rows_per_s": win.steps * stream.rows_per_step / win.seconds,
            "publish_lag_p95_ms": _p95_ms(win.lags_s),
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        reduced = None
        pb = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True))
        if pb:
            neutral = load_xplane(pb[-1])
            if keep_trace:
                save_neutral(neutral, keep_trace)
            reduced = reduce(neutral)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = Ctx(config, kind, sketch_rows, reduced, start, end)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result["breakdown"] = {"device_ops": reduced.top_ops,
                                   "idle_gaps": reduced.idle_gaps}
    result.update(metrics=metrics, device=device)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in found}
    for c in found:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return result
