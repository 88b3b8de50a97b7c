"""The plain reference and the comparison that decides ``correct``.

The reference is exact arithmetic over the rows the benchmark handed in:
float64 Gram matrices ``A^T A`` of each site's rows, whole cycles times
the cycle's Gram plus the Gram of the part cycle.  It imports nothing of
the program and takes nothing the program made.  It runs on the host once
the window has closed.

Three layers are compared, each number beside its limit:

Protocol state (sites and coordinator, as the timed super-steps left it):

* ``mass_gap``: the P1 state's account of every row's mass (mass
  received at the coordinator ``f_c`` plus each site's unshipped ``f_i``)
  against the exact squared Frobenius norm, relative.  Limit from
  readings (the configuration's ``limits``).
* ``sketch_err``: ``||A^T A - B^T B||_2 / (eps ||A||_F^2)`` of the
  coordinator's sketch ``B``: the eps guarantee, limit 1.
* ``state_err``: ``||A^T A - sum_j S_j^T S_j - B^T B||_2`` over
  ``||A||_F^2 (1/l_site + 1/l_coord)``, where ``S_j`` are the sites'
  sketches of what they have not shipped: every row is in a site's
  sketch or was shipped into the coordinator's, each within Frequent
  Directions' bound.  Limit 1.

Published snapshot:

* ``unpublished``: super-steps that no servable snapshot held when the
  ``ingest_many`` that took them returned, plus one if the newest
  snapshot's spectrum was not refreshed at publish.  Exact: limit 0.
* ``publish_gap``: largest difference between the newest snapshot and
  the coordinator sketch in the protocol state.  Exact: limit 0.

Served answers, after the window, for unit directions drawn from the seed
(the reference's top eigenvectors among them):

* ``answer_err``: ``| ||A x||^2 - served |`` over ``eps ||A||_F^2``,
  worst direction, for the answers the router serves (the engine's Pallas
  ``quadform``): the eps guarantee, limit 1.
* ``answer_gap``: ``| ||B x||^2 - served |`` over ``eps ||A||_F^2``, with
  ``||B x||^2`` in float64 from the snapshot the answers were pinned to.
  Limit from readings.
* ``spectrum_gap``: the same for the answers the engine serves from the
  spectrum that publish refreshed (``fd_spectra``).  Limit from readings.

The control (``control=True``) puts the reference in the program's place,
computed in bfloat16, the precision below the configuration's float32:
its state's mass is a running bfloat16 sum of each super-step's mass, and
its answers are ``x^T G x`` evaluated in bfloat16 for the Gram ``G`` it
publishes.  It has to fail.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .traffic import Stream

CHUNK = 1 << 16  # rows per float64 block of the reference


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def _gram(rows: np.ndarray) -> np.ndarray:
    d = rows.shape[1]
    g = np.zeros((d, d))
    for i in range(0, rows.shape[0], CHUNK):
        block = rows[i:i + CHUNK].astype(np.float64)
        g += block.T @ block
    return g


def _spectral(a: np.ndarray) -> float:
    return float(np.max(np.abs(np.linalg.eigvalsh(a))))


class Reference:
    """Exact float64 Gram of the rows handed in over ``steps`` super-steps."""

    def __init__(self, stream: Stream, steps: int):
        self.stream, self.steps = stream, steps
        g = np.zeros((stream.d, stream.d))
        for j in range(stream.m):
            n = stream.site_len(j)
            whole, part = divmod(steps * stream.block, n)
            cycle = stream.site_rows[j][:n]
            if whole:
                g += whole * _gram(cycle)
            g += _gram(cycle[:part])
        self.gram = g
        self.mass = float(np.trace(g))

    def directions(self, seed: int, n: int, top: int) -> np.ndarray:
        """``n`` unit directions: the Gram's ``top`` leading eigenvectors,
        then uniform ones drawn from the seed."""
        _, vecs = np.linalg.eigh(self.gram)
        x = np.random.default_rng([seed, 2]).normal(size=(n, self.stream.d))
        x[:top] = vecs[:, ::-1][:, :top].T
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    def answers(self, x: np.ndarray) -> np.ndarray:
        """Exact ``||A x_i||^2``."""
        return np.einsum("nd,de,ne->n", x, self.gram, x)


class Control:
    """The reference in the program's place, computed in bfloat16."""

    def __init__(self, stream: Stream, steps: int, gram: np.ndarray):
        from ml_dtypes import bfloat16

        self.bf16 = bfloat16
        total = bfloat16(0.0)
        for k in range(steps):
            rows = stream.batch(k).astype(bfloat16)
            step_mass = (rows * rows).sum(axis=1, dtype=bfloat16).sum(dtype=bfloat16)
            total = bfloat16(total + step_mass)
        self.mass = float(total)
        self.published = gram.astype(bfloat16)

    def answers(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(served, exact) for its published Gram: ``x^T G x`` in bfloat16,
        and the same in float64 of the bfloat16 values."""
        bf = self.bf16
        g = self.published
        xb = x.astype(bf)
        served = np.array([
            (xi * (g * xi[None, :]).sum(axis=1, dtype=bf)).sum(dtype=bf) for xi in xb
        ], np.float64)
        g64, x64 = g.astype(np.float64), xb.astype(np.float64)
        return served, np.einsum("nd,de,ne->n", x64, g64, x64)


class Served(NamedTuple):
    """What the program served after the window, for ``directions``."""

    directions: np.ndarray
    answers: np.ndarray  # router.query_batch (Pallas quadform)
    pinned: np.ndarray  # the snapshot those answers were pinned to, (l, d)
    from_spectrum: np.ndarray  # the engine's cached-spectrum path
    refreshed: bool  # that spectrum was refreshed at publish


def compare(ref: Reference, config: dict, *, state: dict, newest: np.ndarray,
            served: Served, unpublished: int, control: Control | None = None
            ) -> list[Check]:
    """Every number, beside its limit (see the module docstring)."""
    eps = float(config["eps"])
    limits = config["limits"]
    mass, gram = ref.mass, ref.gram
    budget = eps * mass

    b = state["coord_buf"][: state["coord_buf"].shape[0] // 2]
    sites = state["site_buf"]
    l_site = sites.shape[1] // 2
    fd_bound = mass * (1.0 / l_site + 1.0 / b.shape[0])
    exact = ref.answers(served.directions)
    if control:
        got_mass = control.mass
        bb = held = control.published.astype(np.float64)
        publish_gap = 0.0
        answers, own = control.answers(served.directions)
        answer_gap = spectrum_gap = float(np.max(np.abs(answers - own))) / budget
    else:
        got_mass = state["f_c"] + float(np.sum(state["f_i"]))
        bb = b.T @ b
        held = np.einsum("mld,mle->de", sites, sites) + bb
        publish_gap = float(np.max(np.abs(newest.astype(np.float64) - b)))
        answers = served.answers
        pinned = np.einsum("nd,ld->nl", served.directions, served.pinned.astype(np.float64))
        on_pinned = np.sum(pinned * pinned, axis=1)
        answer_gap = float(np.max(np.abs(answers - on_pinned))) / budget
        spectrum_gap = float(np.max(np.abs(served.from_spectrum - on_pinned))) / budget
    return [
        Check("unpublished", float(unpublished + (not served.refreshed)), 0.0),
        Check("publish_gap", publish_gap, 0.0),
        Check("mass_gap", abs(got_mass - mass) / mass, float(limits["mass_gap"])),
        Check("sketch_err", _spectral(gram - bb) / budget, 1.0),
        Check("state_err", _spectral(gram - held) / fd_bound, 1.0),
        Check("answer_err", float(np.max(np.abs(exact - answers))) / budget, 1.0),
        Check("answer_gap", answer_gap, float(limits["answer_gap"])),
        Check("spectrum_gap", spectrum_gap, float(limits["spectrum_gap"])),
    ]
