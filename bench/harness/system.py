"""The system under test, built from a configuration file.

One ``ClusterRouter`` over one ``PipelineCell`` whose mesh spans the
configuration's ``m`` sites, one chip each, holding one tenant.  The
benchmark drives it only through the entry points a user calls:
``add_tenant``, ``ingest_many`` and ``query_batch``.  It reads back the
published snapshots, the spectra the engine refreshed at publish, the
tenant's protocol state and the program's counters.
"""
from __future__ import annotations

import numpy as np

TENANT = "t0"


class System:
    """A configuration's tenant behind a ``ClusterRouter``."""

    def __init__(self, config: dict, devices):
        import jax

        from repro.cluster import ClusterRouter, PipelineCell
        from repro.runtime.policies import EveryKSteps

        m = int(config["m"])
        mesh = jax.sharding.Mesh(np.array(devices[:m]), ("sites",))
        self.cell = PipelineCell(
            "cell-0", mesh, axis="sites", eps=float(config["eps"]),
            protocol=config["protocol"], policy=EveryKSteps(int(config["publish_every"])),
        )
        self.router = ClusterRouter([self.cell])
        self.router.add_tenant(TENANT, int(config["stream"]["d"]))

    def ingest(self, rows: np.ndarray) -> int:
        """Hand in one super-step; returns the snapshots published (and
        their spectra refreshed) before it returned."""
        return self.router.ingest_many([(TENANT, rows)])

    def snapshot(self, version: int | None = None):
        """A published ``SketchSnapshot``, the newest by default."""
        return self.cell.store.get(TENANT, version)

    def counters(self) -> dict:
        """The program's metrics registry, as a JSON-able snapshot."""
        return self.router.obs.registry.snapshot()

    def serve(self, x: np.ndarray):
        """Served answers ``||B x_i||^2`` (the engine's Pallas path) and the
        snapshot version they were pinned to."""
        (result,) = self.router.query_batch([(TENANT, x)])
        return np.asarray(result.estimates, np.float64), result.version

    def serve_from_spectrum(self, x: np.ndarray, version: int):
        """Answers from the spectrum the engine cached for ``version``
        (``path="cached"``), and whether that spectrum was already cached,
        refreshed at publish, rather than computed now."""
        engine = self.cell.pipeline.engine
        misses = engine.cache_stats()["spectrum"]["misses"]
        result = engine.query_batch(x, tenant=TENANT, version=version, path="cached")
        refreshed = engine.cache_stats()["spectrum"]["misses"] == misses
        return np.asarray(result.estimates, np.float64), refreshed

    def state(self) -> dict:
        """The tenant's P1 protocol state on the host, float64."""
        st = self.cell.pipeline.tracker(TENANT).state
        return {
            "site_buf": np.asarray(st.site_fd.buf, np.float64),  # (m, 2 l, d)
            "f_i": np.asarray(st.f_i, np.float64),  # (m,) mass since last ship
            "coord_buf": np.asarray(st.coord_fd.buf, np.float64),  # (2 l, d)
            "f_c": float(np.asarray(st.f_c)),  # mass received at the coordinator
        }

    def close(self) -> None:
        self.router.close()
