"""Operation and byte counts, worked by hand, and the peaks table."""
from __future__ import annotations

import pytest
from harness import roofline


def test_fd_spectra_counts():
    l, d = 40, 90
    w = roofline.fd_spectra(sketches=32, l=l, d=d)
    gram = (2 * l * l * d, 4 * (l * d + l * l))
    project = (2 * l * l * d + l * d, 4 * (l + l * l + 2 * l * d))
    assert w.flops == 32 * (gram[0] + project[0])
    assert w.bytes == 32 * (gram[1] + project[1])


def test_share_against_the_v5e_peaks():
    p = roofline.peak("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    # memory-bound work: 819 MB at 819 GB/s needs 1 ms; measured 4 ms -> 25%
    w = roofline.Work(flops=1.0, bytes=819e6)
    assert roofline.share_pct(w, 4e-3, "TPU v5 lite") == pytest.approx(25.0)
    # compute-bound work: 197 GFLOP needs 1 ms; measured 1 ms -> 100%
    w = roofline.Work(flops=197e9, bytes=1.0)
    assert roofline.share_pct(w, 1e-3, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.share_pct(w, 0.0, "TPU v5 lite") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("TPU v4")
