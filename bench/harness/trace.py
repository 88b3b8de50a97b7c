"""Reduce a profiler trace to device busy/idle share, kernel and collective time.

Two steps, so that the arithmetic can be checked on a small recorded trace:

* ``load_xplane(path)`` reads the ``.xplane.pb`` that ``jax.profiler``
  writes into a neutral dict: per device, its op events
  ``[short name, start_ns, dur_ns]`` and its program (module) events
  ``[name, start_ns, dur_ns]``; the spans of the host's Python thread
  ``[name, start_ns, dur_ns]``; and the traced window ``[start_ns,
  end_ns]`` (the host span ``bench.window`` that the benchmark wraps
  around its measured loop).
* ``reduce(neutral)`` turns that into a ``Reduced``: busy seconds as the
  union of op intervals inside the window, averaged over devices; device
  time per kernel (``KERNELS``, the benchmark's own map from the names the
  trace shows to kernels: the Pallas kernels carry no ``name=``), summed
  over devices, since a kernel may run on one device only; program
  launches and time per program (``PROGRAMS``), averaged over devices (one
  SPMD launch runs on each); the top device ops; and
  the longest idle gaps, each put down to the innermost host span that
  covers it.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import NamedTuple

WINDOW_SPAN = "bench.window"

# Kernel -> regular expression on an op's short name, ``<hlo name>`` with
# ``[<target>]`` for a custom call (see ``_short``).  A Pallas kernel is a
# ``tpu_custom_call`` named after the jitted function that launches it.
KERNELS = {
    "fd_spectra": r"^_fd_spectra_fused(\.\d+)?\[tpu_custom_call\]$",
    "all_gather": r"^all-gather(-start|-done)?(\.\d+)?$",
}

# Program -> regular expression on the XLA module name of one launch
# (``jit_<function>(<fingerprint>)``).
PROGRAMS = {
    "superstep": r"^jit_(step_stacked|step_from_states|_inner)\(",
}


class Reduced(NamedTuple):
    """What the per-layer metrics read from one traced window."""

    window_s: float
    busy_s: float  # mean over devices
    devices: int
    kernel_s: dict  # kernel -> device seconds, summed over devices
    kernel_n: dict  # kernel -> events, summed over devices
    program_s: dict  # program -> device seconds, mean over devices
    program_n: dict  # program -> launches, mean over devices
    top_ops: list  # [[name, seconds], ...] mean over devices, longest first
    idle_gaps: list  # [[host span, seconds], ...] on device 0, longest first


def _short(hlo: str) -> str:
    """An op's short name from the HLO text the trace shows
    (``%<name> = <shape> <opcode>(...), ...``): its name, and for a custom
    call its target in brackets."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name}[{target.group(1)}]" if target else name


def _op_key(short: str) -> str:
    """The short name without its ``.<n>`` instance number."""
    return re.sub(r"\.\d+(?=\[|$)", "", short)


def load_xplane(path: str | Path) -> dict:
    """Neutral events of one ``.xplane.pb`` (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([_short(e.name), int(e.start_ns), int(e.duration_ns)]
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                   for e in line.events)
            devices.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):  # the interpreter's own spans
                    continue
                for e in line.events:
                    span = [e.name, int(e.start_ns), int(e.duration_ns)]
                    host.append(span)
                    if e.name == WINDOW_SPAN:
                        window = [span[1], span[1] + span[2]]
    devices.sort(key=lambda dv: dv["name"])
    return {"devices": devices, "host": host, "window": window}


def save_neutral(neutral: dict, path: str | Path) -> None:
    """Write neutral events as gzipped JSON (for a recorded test trace)."""
    with gzip.open(path, "wt") as f:
        json.dump(neutral, f)


def load_neutral(path: str | Path) -> dict:
    """Read what ``save_neutral`` wrote."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _clip(start: int, dur: int, lo: int, hi: int) -> tuple[int, int] | None:
    a, b = max(start, lo), min(start + dur, hi)
    return (a, b) if b > a else None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _attribute(gaps: list[tuple[int, int]], host: list) -> dict[str, float]:
    """Seconds of each gap, keyed by the shortest host span covering its
    midpoint (one sweep over spans and midpoints, both in time order)."""
    import heapq

    spans = sorted(host, key=lambda s: s[1])
    active: list = []  # (duration, end, name), shortest on top
    out: dict[str, float] = {}
    j = 0
    for a, b in sorted(gaps):
        t = (a + b) // 2
        while j < len(spans) and spans[j][1] <= t:
            name, start, dur = spans[j]
            heapq.heappush(active, (dur, start + dur, name))
            j += 1
        while active and active[0][1] < t:  # spans ended before t never return
            heapq.heappop(active)
        what = active[0][2] if active else "no host span"
        out[what] = out.get(what, 0.0) + (b - a) * 1e-9
    return out


def reduce(neutral: dict, *, top: int = 10) -> Reduced | None:
    """``Reduced`` of one traced window, or None when it holds no device."""
    devices, window = neutral["devices"], neutral["window"]
    if not devices or window is None:
        return None
    lo, hi = window
    n = len(devices)
    busy = 0.0
    kernel_s = {k: 0.0 for k in KERNELS}
    kernel_n = {k: 0.0 for k in KERNELS}
    program_s = {p: 0.0 for p in PROGRAMS}
    program_n = {p: 0.0 for p in PROGRAMS}
    op_s: dict[str, float] = {}
    gaps0: list[tuple[int, int]] = []
    for i, dev in enumerate(devices):
        spans = []
        for name, start, dur in dev["ops"]:
            c = _clip(start, dur, lo, hi)
            if c is None:
                continue
            spans.append(c)
            secs = (c[1] - c[0]) * 1e-9
            key = _op_key(name)
            op_s[key] = op_s.get(key, 0.0) + secs / n
            for k, pat in KERNELS.items():
                if re.search(pat, name):
                    kernel_s[k] += secs
                    kernel_n[k] += 1.0
        for name, start, dur in dev["modules"]:
            c = _clip(start, dur, lo, hi)
            if c is None:
                continue
            for p, pat in PROGRAMS.items():
                if re.search(pat, name):
                    program_s[p] += (c[1] - c[0]) * 1e-9 / n
                    program_n[p] += 1.0 / n
        merged = _union(spans)
        busy += sum(b - a for a, b in merged) * 1e-9 / n
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps0 = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                     if edges[j + 1] > edges[j]]
    by_host = _attribute(gaps0, neutral["host"])
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy,
        devices=n,
        kernel_s=kernel_s,
        kernel_n=kernel_n,
        program_s=program_s,
        program_n=program_n,
        top_ops=sorted(([k, v] for k, v in op_s.items()), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(([k, v] for k, v in by_host.items()), key=lambda kv: -kv[1])[:top],
    )
