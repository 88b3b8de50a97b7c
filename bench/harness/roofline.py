"""Operations and bytes of the algorithm, and the chip's peaks.

The counts come from the shapes of the work, not from ``cost_analysis``,
so that they read the same whatever implements it: padding, tiling and
extra passes of an implementation do not count as work.  Every array is
float32 (4 bytes), as the program serves it.

A roofline share is the least time the chip could take for that work,
the larger of operations over peak FLOP/s and bytes over peak bandwidth,
divided by the measured device time.
"""
from __future__ import annotations

from typing import NamedTuple

F32 = 4


class Peak(NamedTuple):
    flops: float  # FLOP/s
    hbm_bytes_per_s: float
    source: str


# Keyed by ``jax.Device.device_kind``.  The FLOP/s peak is the bf16 one:
# the program's float32 matmuls run at default precision, one bf16 pass
# on the MXU.
PEAKS = {
    "TPU v5 lite": Peak(
        flops=197e12,
        hbm_bytes_per_s=819e9,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip",
    ),
}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to PEAKS "
                       "with its source") from None


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def fd_spectra(sketches: int, l: int, d: int) -> Work:
    """Gram ``B B^T`` and projection ``diag(w) U^T B`` of (l, d) sketches.

    The Gram reads B and writes (l, l); the projection reads the weights,
    U and B and writes (l, d).  The ``eigh`` between them is XLA's, not
    one of these kernels, and is not counted.
    """
    gram = Work(2.0 * l * l * d, F32 * (l * d + l * l))
    project = Work(2.0 * l * l * d + l * d, F32 * (l + l * l + 2 * l * d))
    total = gram + project
    return Work(sketches * total.flops, sketches * total.bytes)


def share_pct(work: Work, seconds: float, device_kind: str) -> float | None:
    """Percent of the roofline reached by ``work`` in ``seconds`` of device
    time; None when nothing was measured."""
    if seconds <= 0 or work.flops <= 0:
        return None
    p = peak(device_kind)
    least = max(work.flops / p.flops, work.bytes / p.hbm_bytes_per_s)
    return 100.0 * least / seconds
