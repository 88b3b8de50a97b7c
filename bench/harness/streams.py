"""The paper's Section 6 streams and site placement, generated from a seed.

Copied from ``src/repro/data/synthetic.py`` so that a change to the
program cannot move the benchmark's inputs.  PAMAP (629,250 x 44, rank
about 25) and YearPredictionMSD (300,000 x 90, full rank) cannot ship
offline; the generators match their published size, width and rank, with
squared row norms uniform in ``[1, beta]`` (the paper's weight model).
"""
from __future__ import annotations

import numpy as np

__all__ = ["GENERATORS", "msd_like", "pamap_like", "site_assignment"]


def _scaled_rows(a: np.ndarray, rng, beta: float) -> np.ndarray:
    """Rescale rows to squared norms in [1, beta]."""
    norms = np.sqrt(np.maximum(np.einsum("nd,nd->n", a, a), 1e-12))
    target = np.sqrt(rng.uniform(1.0, beta, size=a.shape[0]))
    return a * (target / norms)[:, None]


def pamap_like(n: int, d: int, *, beta: float, seed: int) -> np.ndarray:
    """Low-rank-plus-noise rows (PAMAP is about rank 25 in 44 dimensions)."""
    rng = np.random.default_rng(seed)
    rank = 25
    spectrum = np.exp(-0.35 * np.arange(rank))
    u = rng.normal(size=(n, rank)) * spectrum[None, :]
    v = rng.normal(size=(rank, d)) / np.sqrt(d)
    a = u @ v + 1e-4 * rng.normal(size=(n, d))
    return _scaled_rows(a, rng, beta)


def msd_like(n: int, d: int, *, beta: float, seed: int) -> np.ndarray:
    """Full-rank heavy-tailed rows (MSD keeps its error even at rank 50)."""
    rng = np.random.default_rng(seed)
    spectrum = (1.0 + np.arange(d)) ** -0.35
    u = rng.normal(size=(n, d)) * spectrum[None, :]
    v, _ = np.linalg.qr(rng.normal(size=(d, d)))
    a = u @ v.T
    return _scaled_rows(a, rng, beta)


def site_assignment(n: int, m: int, *, seed: int) -> np.ndarray:
    """Uniform-random site for each stream row (the paper's model lets any
    site receive any row)."""
    return np.random.default_rng(seed + 7).integers(0, m, size=n)


GENERATORS = {"pamap_like": pamap_like, "msd_like": msd_like}
