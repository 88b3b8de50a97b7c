"""The trace reducer on a hand-made trace and on a recorded v5e trace.

``data/v5e_ingest_trace.json.gz`` is 1.1 s cut from a traced run of a
32-tenant MSD-like packed ingest loop on a TPU v5e (two packed super-steps, their
publishes and spectrum refreshes), in the reducer's neutral format; host
spans shorter than 0.2 ms were left out to keep the file small.
"""
from __future__ import annotations

import re
from pathlib import Path

import numpy as np
import pytest
from harness import trace

DATA = Path(__file__).parent / "data" / "v5e_ingest_trace.json.gz"


def _hand_made() -> dict:
    # window [100, 1100) ns; device 0 busy [100,300) u [250,400) u [900,1100)
    # (an op reaching past each edge is clipped); device 1 busy [500,700)
    return {
        "window": [100, 1100],
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [["_fd_spectra_fused.3[tpu_custom_call]", 50, 250],
                     ["fusion.1", 250, 150],
                     ["all-gather.2", 900, 400]],
             "modules": [["jit_step_stacked(123)", 240, 170], ["jit_other(9)", 0, 50]]},
            {"name": "/device:TPU:1",
             "ops": [["all-gather.7", 500, 200]],
             "modules": [["jit_step_stacked(123)", 500, 200]]},
        ],
        # gaps on device 0: [400, 900), midpoint 650
        "host": [["bench.window", 100, 1000], ["bench.poll", 600, 100],
                 ["short.before", 640, 5]],
    }


def test_reduce_hand_made():
    r = trace.reduce(_hand_made())
    assert r.window_s == pytest.approx(1000e-9)
    assert r.devices == 2
    # device 0: 300 + 200 = 500 ns; device 1: 200 ns; mean 350 ns
    assert r.busy_s == pytest.approx(350e-9)
    assert r.kernel_s["fd_spectra"] == pytest.approx(200e-9)  # clipped to [100, 300)
    assert r.kernel_n["fd_spectra"] == pytest.approx(1.0)
    assert r.kernel_s["all_gather"] == pytest.approx(200e-9 + 200e-9)  # both devices
    assert r.program_n["superstep"] == pytest.approx(1.0)
    assert r.program_s["superstep"] == pytest.approx((170e-9 + 200e-9) / 2)
    # one gap, [400, 900): the shortest span covering 650 is bench.poll
    assert r.idle_gaps == [["bench.poll", pytest.approx(500e-9)]]
    assert dict(r.top_ops)["_fd_spectra_fused[tpu_custom_call]"] == pytest.approx(100e-9)


def test_reduce_without_device_or_window():
    t = _hand_made()
    assert trace.reduce({**t, "devices": []}) is None
    assert trace.reduce({**t, "window": None}) is None


def test_reduce_recorded_v5e_trace():
    neutral = trace.load_neutral(DATA)
    r = trace.reduce(neutral)
    lo, hi = neutral["window"]
    # busy time, independently: a 1 us timeline of the window
    busy = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, start, dur in neutral["devices"][0]["ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            busy[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    assert r.busy_s == pytest.approx(busy.sum() * 1e-6, abs=2e-3)
    assert 0.0 < r.busy_s < r.window_s == pytest.approx(1.1)
    # what the recording holds: two super-step launches, whose batched eigh
    # dominates, and four spectrum kernels (Gram + projection, twice)
    assert r.program_n["superstep"] == pytest.approx(2.0)
    assert r.kernel_n["fd_spectra"] == pytest.approx(4.0)
    assert r.kernel_n["all_gather"] == 0
    names = [n for n, _ in r.top_ops]
    assert "custom-call[EighTpu]" in names[:3]
    pattern = re.compile(trace.KERNELS["fd_spectra"])
    direct = sum(min(s + d, hi) - max(s, lo) for n, s, d in neutral["devices"][0]["ops"]
                 if pattern.search(n)) * 1e-9
    assert r.kernel_s["fd_spectra"] == pytest.approx(direct)
    gaps = sum(s for _, s in r.idle_gaps)
    assert gaps <= r.window_s - r.busy_s + 1e-9
