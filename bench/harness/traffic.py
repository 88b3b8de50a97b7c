"""The one general generator: super-step batches from a seed.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``);
this module is the only code that reads it.  Keys:

* ``loop``: ``"closed"``, one super-step handed in when the last returned
  (the only loop so far);
* ``rows_per_site``: rows each site receives per super-step;
* ``warm_steps``: super-steps ingested during set-up (they compile every
  program the window runs, and their rows count in the check).

The configuration names the stream (generator, rows, width, beta) and the
number of sites ``m``.  Every row of the stream goes to a site drawn
uniformly from the seed (``site_assignment``); each site reads its own
rows in stream order, from an offset drawn from the seed, and cycles
them.  Super-step ``k`` hands site ``j`` its rows ``[k b, (k + 1) b)`` of
that cycle (``b`` = ``rows_per_site``), and the global batch is the sites'
blocks one after the other, as the mesh shards it.  Every seed gives the
same sizes; the seed moves only the values and the offsets.
"""
from __future__ import annotations

import numpy as np

from .streams import GENERATORS, site_assignment


class Stream:
    """A configuration's rows, split over its sites, and the super-steps."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        s = config["stream"]
        self.d = int(s["d"])
        self.m = int(config["m"])
        self.block = int(traffic["rows_per_site"])
        rows = int(s["rows"])
        pool = GENERATORS[s["generator"]](rows, self.d, beta=float(s["beta"]), seed=seed)
        pool = pool.astype(np.float32)
        site_of = site_assignment(rows, self.m, seed=seed)
        offsets = np.random.default_rng([seed, 1]).integers(0, rows, size=self.m)
        # Each site's rows rotated to start at its offset, with the first
        # block appended, so that every super-step's block is a view.
        self.site_rows: list[np.ndarray] = []
        for j in range(self.m):
            own = pool[site_of == j]
            if own.shape[0] < self.block:
                raise ValueError(f"site {j} holds {own.shape[0]} rows, fewer than a block")
            own = np.roll(own, -int(offsets[j] % own.shape[0]), axis=0)
            self.site_rows.append(np.concatenate([own, own[: self.block]]))

    def site_len(self, j: int) -> int:
        """Rows in site ``j``'s cycle."""
        return self.site_rows[j].shape[0] - self.block

    def site_block(self, j: int, step: int) -> np.ndarray:
        """Site ``j``'s ``(rows_per_site, d)`` rows of super-step ``step``."""
        start = (step * self.block) % self.site_len(j)
        return self.site_rows[j][start:start + self.block]

    def batch(self, step: int) -> np.ndarray:
        """The ``(m * rows_per_site, d)`` rows of super-step ``step``."""
        if self.m == 1:
            return self.site_block(0, step)
        return np.concatenate([self.site_block(j, step) for j in range(self.m)])

    @property
    def rows_per_step(self) -> int:
        """Rows handed in per super-step."""
        return self.m * self.block
