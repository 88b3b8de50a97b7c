"""Run the tracking system's main path once on a TPU and check its answers.

    python chip_smoke.py             # one chip: paper-scale streams, tenant fleet
    python chip_smoke.py --chips 4   # four chips: 4-site mesh, 4 router cells

Every phase drives the entry points a user calls, in this one process:
``ClusterRouter`` -> ``PipelineCell`` -> ``StreamingPipeline`` (shard
engine) -> ``SketchStore`` -> ``QueryEngine`` / packed query service.
Streams come from ``repro.data.synthetic`` with ``--seed``; every answer is
checked against an exact float64 reference within its eps bound.

Each phase prints one JSON line: rows ingested, wall and compile seconds
(set-up, not speed), the largest error over its eps bound, the largest
Pallas-vs-XLA relative gap on the same snapshot, and whether the compiled
query and publish programs hold a Pallas TPU kernel (``tpu_custom_call``).
A failed check raises; the last line, printed only when all passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script refuses to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

EPS = 0.1
WINDOW, WINDOW_BUCKETS = 16.0, 4  # event-time units; bucket width 4


class Sizes(NamedTuple):
    """Streams as (batches, rows per batch); the defaults are paper scale."""

    pamap: tuple[int, int] = (150, 4_195)  # 629,250 x 44, PAMAP-like
    msd: tuple[int, int] = (75, 4_000)  # 300,000 x 90, MSD-like
    fleet: tuple[int, int] = (10, 2_000)  # 20,000 MSD-like rows per tenant
    items: tuple[int, int] = (50, 4_000)  # 200,000 HH / quantile items
    leverage: tuple[int, int] = (25, 2_000)  # 50,000 x 90
    window: tuple[int, int] = (29, 2_000)  # 29 event-time batches
    fleet_tenants: int = 32
    router_tenants: int = 12  # matrix tenants spread over the 4 router cells
    queries: int = 1024

    def cuts(self) -> dict:
        """Every size below the paper scale, as ``{field: (full, used)}``."""
        full = Sizes()
        return {f: (getattr(full, f), getattr(self, f))
                for f in self._fields if getattr(self, f) != getattr(full, f)}


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses seen by this process.

    Compile time is JAX's own trace + lowering + backend-compile durations
    (a persistent-cache hit replaces the backend compile by a read).
    """

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event in self._DURATIONS:
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc_info) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def totals(self) -> tuple[float, int, int]:
        """(compile seconds, cache hits, cache misses) so far."""
        return self.seconds, self.cache_hits, self.cache_misses


class Phase:
    """Times one phase and collects the fields of its report line."""

    def __init__(self, name: str, meter: CompileMeter):
        self.name = name
        self.meter = meter
        self.report: dict = {"phase": name, "rows": 0, "err_over_bound": {},
                             "pallas_vs_xla_rel_gap": {}, "tpu_custom_call": {}}

    def __enter__(self) -> "Phase":
        self._t0 = time.perf_counter()
        self._c0 = self.meter.totals()
        return self

    def __exit__(self, exc_type, *_) -> None:
        if exc_type is not None:
            return
        c1 = self.meter.totals()
        self.report["wall_s_setup"] = time.perf_counter() - self._t0
        self.report["compile_s_setup"] = c1[0] - self._c0[0]
        self.report["cache_hits"] = c1[1] - self._c0[1]
        self.report["cache_misses"] = c1[2] - self._c0[2]
        print(json.dumps(self.report), flush=True)

    def bound(self, check: str, err: float, bound: float) -> None:
        """Record ``err / bound`` for one check; raise if it exceeds 1."""
        ratio = float(err) / float(bound)
        self.report["err_over_bound"][check] = ratio
        if not ratio <= 1.0:
            raise AssertionError(
                f"{self.name}/{check}: error {err} exceeds its eps bound {bound}"
            )

    def gap(self, what: str, got, want, *, elementwise: bool = True) -> None:
        """Record the largest Pallas-vs-XLA gap for one program: relative to
        the largest XLA answer (``norm``) and, where every answer is a
        positive quadratic form, per answer (``elem``)."""
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        diff = np.abs(got - want)
        gaps = {"norm": float(np.max(diff) / max(float(np.max(np.abs(want))), 1e-30))}
        if elementwise:
            gaps["elem"] = float(np.max(diff / np.maximum(np.abs(want), 1e-30)))
        self.report["pallas_vs_xla_rel_gap"][what] = gaps

    def kernel(self, what: str, fn, *args) -> None:
        """Record whether ``fn(*args)``, as this backend compiles it, holds
        a Pallas TPU kernel."""
        import jax

        text = jax.jit(fn).lower(*args).compile().as_text()
        self.report["tpu_custom_call"][what] = "tpu_custom_call" in text


# -- streams and exact references -------------------------------------------


def _f32(a: np.ndarray) -> np.ndarray:
    """Rows as the pipeline ingests them; exact references use these too."""
    return np.asarray(a, np.float32)


def _split(a: np.ndarray, batches: int) -> list[np.ndarray]:
    return np.split(np.asarray(a, np.float32), batches)


def _unit_directions(rng: np.random.Generator, n: int, d: int) -> np.ndarray:
    x = rng.normal(size=(n, d))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _gram(a: np.ndarray) -> np.ndarray:
    a64 = np.asarray(a, np.float64)
    return a64.T @ a64


def _quad(gram: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Exact ``||A x_j||^2`` from A's float64 Gram."""
    x64 = np.asarray(x, np.float64)
    return np.einsum("nd,de,ne->n", x64, gram, x64)


def _spectrum_gram(s: np.ndarray, vt: np.ndarray) -> np.ndarray:
    """``B^T B`` rebuilt from stacked spectra: sign-free, unlike ``vt``."""
    s64, v64 = np.asarray(s, np.float64), np.asarray(vt, np.float64)
    return np.einsum("tl,tld,tle->tde", s64**2, v64, v64)


def _make_router(meshes, *, axis: str, max_batch: int):
    from repro.cluster import ClusterRouter, PipelineCell

    cells = [PipelineCell(f"cell-{i}", mesh, axis=axis, eps=EPS, max_batch=max_batch)
             for i, mesh in enumerate(meshes)]
    return ClusterRouter(cells)


def _serve(router, queries: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Submit every row of every tenant's queries, flush, read the answers."""
    tickets = {t: [router.submit(t, row) for row in x] for t, x in queries.items()}
    router.flush()
    return {t: np.array([tk.result()[0] for tk in ts], np.float64)
            for t, ts in tickets.items()}


def _check_matrix(phase, name: str, got, gram, x, eps_mass: float) -> None:
    phase.bound(name, np.max(np.abs(_quad(gram, x) - got)), eps_mass)


def _matrix_kernels(phase, engine, stack: np.ndarray, x: np.ndarray, served) -> None:
    """Pallas-vs-XLA gaps and kernel presence for the quadform + publish
    programs on one stack of same-shape snapshots ``(T, l, d)``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import fd_spectra, quadform_packed
    from repro.kernels.ref import ref_quadform_packed

    t, n = stack.shape[0], x.shape[1]
    phase.gap("quadform", served, jax.jit(ref_quadform_packed)(stack, x))
    s_p, vt_p = fd_spectra(jnp.asarray(stack), path="pallas", interpret=engine.interpret)
    s_x, vt_x = fd_spectra(jnp.asarray(stack), path="xla")
    phase.gap("fd_spectra_s", s_p, s_x, elementwise=False)
    phase.gap("fd_spectra_gram", _spectrum_gram(s_p, vt_p), _spectrum_gram(s_x, vt_x),
              elementwise=False)
    f32 = jnp.float32
    phase.kernel("query", lambda b, q: quadform_packed(b, q, interpret=engine.interpret),
                 jax.ShapeDtypeStruct(stack.shape, f32),
                 jax.ShapeDtypeStruct((t, n, stack.shape[2]), f32))
    phase.kernel("publish", lambda b: fd_spectra(b, interpret=engine.interpret),
                 jax.ShapeDtypeStruct(stack.shape, f32))


# -- one-chip phases ------------------------------------------------------------


def phase_paper_streams(meter, devices, sizes: Sizes, seed: int) -> dict:
    """PAMAP-like and MSD-like streams as P2 tenants on one cell."""
    import jax

    from repro.data.synthetic import msd_like, pamap_like

    with Phase("paper_streams", meter) as ph:
        streams = {
            "pamap": _f32(pamap_like(sizes.pamap[0] * sizes.pamap[1], 44, seed=seed)),
            "msd": _f32(msd_like(sizes.msd[0] * sizes.msd[1], 90, seed=seed + 1)),
        }
        batches = {"pamap": sizes.pamap[0], "msd": sizes.msd[0]}
        mesh = jax.sharding.Mesh(np.array(devices[:1]), ("sites",))
        router = _make_router([mesh], axis="sites", max_batch=2 * sizes.queries)
        for name, a in streams.items():
            router.add_tenant(name, a.shape[1], protocol="P2")
        router.ingest_many(
            [(name, rows) for name, a in streams.items()
             for rows in _split(a, batches[name])]
        )
        ph.report["rows"] = sum(a.shape[0] for a in streams.values())
        rng = np.random.default_rng(seed + 2)
        x = {name: _unit_directions(rng, sizes.queries, a.shape[1])
             for name, a in streams.items()}
        got = _serve(router, x)
        engine = router.cell_for("msd").engine
        for name, a in streams.items():
            gram = _gram(a)
            _check_matrix(ph, name, got[name], gram, x[name], EPS * np.trace(gram))
            snap = router.cell_for(name).store.get(name)
            stack = np.asarray(snap.matrix)[None]
            if name == "msd":
                _matrix_kernels(ph, engine, stack, x[name][None], got[name][None])
            else:
                from repro.kernels.ref import ref_quadform

                ph.gap("quadform_pamap", got[name],
                       jax.jit(ref_quadform)(stack[0], x[name]))
        router.close()
    return ph.report


def phase_fleet(meter, devices, sizes: Sizes, seed: int) -> dict:
    """A mixed tenant fleet on two cells: 32 packed P2 tenants, HH,
    quantile, leverage and one sliding-window matrix tenant."""
    import jax
    import jax.numpy as jnp

    from repro.core.leverage import score_query, subspace_query
    from repro.core.quantiles import quantile_query, rank_query
    from repro.data.synthetic import msd_like, zipfian_stream
    from repro.kernels.ops import levscore
    from repro.runtime.registry import get_spec

    with Phase("fleet", meter) as ph:
        d = 90
        n_t = sizes.fleet_tenants
        waves, rows_per = sizes.fleet
        mats = {f"mat-{i:02d}": _f32(msd_like(waves * rows_per, d, seed=seed + 10 + i))
                for i in range(n_t)}
        n_items = sizes.items[0] * sizes.items[1]
        keys, weights = zipfian_stream(n_items, seed=seed + 3)
        weights = weights.astype(np.float32)
        rng = np.random.default_rng(seed + 4)
        values = rng.gamma(2.0, 10.0, n_items).astype(np.float32)
        lev = _f32(msd_like(sizes.leverage[0] * sizes.leverage[1], d, seed=seed + 5))
        win_batches, win_rows = sizes.window
        win = _split(_f32(msd_like(win_batches * win_rows, d, seed=seed + 6)), win_batches)

        meshes = [jax.sharding.Mesh(np.array(devices[:1]), ("sites",))] * 2
        router = _make_router(meshes, axis="sites", max_batch=(n_t + 8) * sizes.queries)
        for name in mats:
            router.add_tenant(name, d, protocol="P2")
        router.add_hh_tenant("hh", engine="shard")
        router.add_quantile_tenant("quantile", engine="shard")
        router.add_leverage_tenant("leverage", d, engine="shard")
        router.add_windowed_tenant("window", kind="matrix", d=d, engine="shard",
                                   window=WINDOW, buckets=WINDOW_BUCKETS)

        hh_pairs = np.stack([keys, weights], axis=1).astype(np.float32)
        q_pairs = np.stack([values, np.ones_like(values)], axis=1)
        feed = [(name, rows) for name, a in mats.items() for rows in _split(a, waves)]
        feed += [("hh", p) for p in _split(hh_pairs, sizes.items[0])]
        feed += [("quantile", p) for p in _split(q_pairs, sizes.items[0])]
        feed += [("leverage", r) for r in _split(lev, sizes.leverage[0])]
        feed += [("window", r, float(ts)) for ts, r in enumerate(win)]
        router.ingest_many(feed, packed=True)
        ph.report["rows"] = (n_t * waves * rows_per + 2 * n_items + lev.shape[0]
                             + win_batches * win_rows)

        nq = sizes.queries
        x = {name: _unit_directions(rng, nq, d) for name in [*mats, "window"]}
        exact_w = np.bincount(keys, weights=np.asarray(weights, np.float64))
        hot = np.argsort(exact_w)[::-1][: nq // 2]
        ids = np.concatenate([hot, rng.integers(0, exact_w.shape[0], nq - hot.size)])
        order = np.sort(values)
        probes = np.quantile(order, rng.uniform(0.0, 1.0, nq // 2)).astype(np.float32)
        phis = rng.uniform(0.0, 1.0, nq - probes.size)
        lev_x = _unit_directions(rng, nq, d)
        half = nq // 2
        queries = {**x, "hh": ids[:, None].astype(np.float32)}
        queries["quantile"] = np.stack(
            [rank_query(v) for v in probes] + [quantile_query(p) for p in phis])
        queries["leverage"] = np.stack(
            [subspace_query(v) for v in lev_x[:half]] + [score_query(v) for v in lev_x[half:]])
        got = _serve(router, queries)

        for name, a in mats.items():
            gram = _gram(a)
            _check_matrix(ph, name, got[name], gram, x[name], EPS * np.trace(gram))
        kept = np.concatenate([r for ts, r in enumerate(win) if ts >= win_batches - 1 - WINDOW])
        gram = _gram(kept)
        _check_matrix(ph, "window", got["window"], gram, x["window"], EPS * np.trace(gram))

        w_total = float(np.sum(np.asarray(weights, np.float64)))
        ph.bound("hh", np.max(np.abs(got["hh"] - exact_w[ids])),
                 get_spec("P1", "shard", "hh").err_factor * EPS * w_total)
        q_bound = get_spec("P1", "shard", "quantile").err_factor * EPS * n_items
        rank_err = np.abs(got["quantile"][: probes.size]
                          - np.searchsorted(order, probes, side="right"))
        v_hat = got["quantile"][probes.size:].astype(np.float32)
        target = phis * n_items
        below = np.searchsorted(order, v_hat, side="left")
        upto = np.searchsorted(order, v_hat, side="right")
        phi_err = np.maximum(np.maximum(below - target, target - upto), 0.0)
        ph.bound("quantile_rank", np.max(rank_err), q_bound)
        ph.bound("quantile_phi", np.max(phi_err), q_bound)
        lev_gram = _gram(lev)
        ph.bound("leverage_subspace",
                 np.max(np.abs(_quad(lev_gram, lev_x[:half]) - got["leverage"][:half])),
                 get_spec("P1", "shard", "leverage").err_factor * EPS * np.trace(lev_gram))

        names = sorted(mats)
        engine = router.cell_for(names[0]).engine
        stack = np.stack([np.asarray(router.cell_for(n).store.get(n).matrix) for n in names])
        _matrix_kernels(ph, engine, stack, np.stack([x[n] for n in names]),
                        np.stack([got[n] for n in names]))
        lev_engine = router.cell_for("leverage").engine
        factor = jnp.asarray(lev_engine._factor_for(
            router.cell_for("leverage").store.get("leverage")), jnp.float32)
        score_x = jnp.asarray(lev_x[half:])
        served = got["leverage"][half:]
        ph.gap("levscore", served, levscore(factor, score_x, path="xla"))
        ph.kernel("levscore", lambda m, q: levscore(m, q, interpret=lev_engine.interpret),
                  jax.ShapeDtypeStruct(factor.shape, jnp.float32),
                  jax.ShapeDtypeStruct(score_x.shape, jnp.float32))
        router.close()
    return ph.report


# -- four-chip phases ----------------------------------------------------------


def _device_set(tree) -> set:
    import jax

    out: set = set()
    for leaf in jax.tree.leaves(tree):
        out |= set(leaf.sharding.device_set)
    return out


def phase_sites_mesh(meter, devices, sizes: Sizes, seed: int) -> dict:
    """The paper's model: m = 4 sites, one per chip, under one coordinator."""
    import jax

    from repro.data.synthetic import msd_like, zipfian_stream
    from repro.runtime.registry import get_spec

    with Phase("sites_mesh", meter) as ph:
        mesh = jax.sharding.Mesh(np.array(devices[:4]), ("sites",))
        router = _make_router([mesh], axis="sites", max_batch=2 * sizes.queries)
        a = _f32(msd_like(sizes.msd[0] * sizes.msd[1], 90, seed=seed + 1))
        n_items = sizes.items[0] * sizes.items[1]
        keys, weights = zipfian_stream(n_items, seed=seed + 3)
        weights = weights.astype(np.float32)
        router.add_tenant("msd", 90, protocol="P2")
        router.add_hh_tenant("hh", engine="shard")
        pairs = np.stack([keys, weights], axis=1).astype(np.float32)
        router.ingest_many([("msd", r) for r in _split(a, sizes.msd[0])]
                           + [("hh", p) for p in _split(pairs, sizes.items[0])])
        ph.report["rows"] = a.shape[0] + n_items

        pipe = router.cell_for("msd").pipeline
        site_devices = {
            "msd": _device_set(pipe.tracker("msd").state.site_fd),
            "hh": _device_set(pipe.tracker("hh").state.site_mg),
        }
        ph.report["site_state_devices"] = {k: len(v) for k, v in site_devices.items()}
        for name, devs in site_devices.items():
            if devs != set(devices[:4]):
                raise AssertionError(f"{name}: per-site state spans {devs}, not the 4 chips")

        rng = np.random.default_rng(seed + 2)
        x = _unit_directions(rng, sizes.queries, 90)
        exact_w = np.bincount(keys, weights=np.asarray(weights, np.float64))
        ids = np.argsort(exact_w)[::-1][: sizes.queries]
        got = _serve(router, {"msd": x, "hh": ids[:, None].astype(np.float32)})
        gram = _gram(a)
        _check_matrix(ph, "msd", got["msd"], gram, x, EPS * np.trace(gram))
        ph.bound("hh", np.max(np.abs(got["hh"] - exact_w[ids])),
                 get_spec("P1", "shard", "hh").err_factor * EPS * float(np.sum(weights)))
        router.close()
    return ph.report


def phase_router_cells(meter, devices, sizes: Sizes, seed: int) -> dict:
    """Four router cells, one per chip, answer byte-identically to one
    pipeline on chip 0."""
    import jax

    from repro.core.leverage import subspace_query
    from repro.core.quantiles import rank_query
    from repro.data.synthetic import msd_like, zipfian_stream
    from repro.query import PackedRequest
    from repro.runtime import StreamingPipeline

    with Phase("router_cells", meter) as ph:
        d = 90
        waves, rows_per = sizes.fleet
        mats = {f"mat-{i:02d}": _f32(msd_like(waves * rows_per, d, seed=seed + 10 + i))
                for i in range(sizes.router_tenants)}
        n_items = sizes.items[0] * sizes.items[1]
        keys, weights = zipfian_stream(n_items, seed=seed + 3)
        weights = weights.astype(np.float32)
        values = np.random.default_rng(seed + 4).gamma(2.0, 10.0, n_items).astype(np.float32)
        lev = _f32(msd_like(sizes.leverage[0] * sizes.leverage[1], d, seed=seed + 5))
        feed = [(name, r) for name, a in mats.items() for r in _split(a, waves)]
        feed += [("hh", p) for p in _split(np.stack([keys, weights], 1), sizes.items[0])]
        feed += [("quantile", p) for p in
                 _split(np.stack([values, np.ones_like(values)], 1), sizes.items[0])]
        feed += [("leverage", r) for r in _split(lev, sizes.leverage[0])]

        def build(target):
            for name in mats:
                target.add_tenant(name, d, protocol="P2")
            target.add_hh_tenant("hh", engine="shard")
            target.add_quantile_tenant("quantile", engine="shard")
            target.add_leverage_tenant("leverage", d, engine="shard")
            for tenant, rows in feed:
                target.ingest(tenant, rows)

        meshes = [jax.sharding.Mesh(np.array([dev]), ("sites",)) for dev in devices[:4]]
        single = StreamingPipeline(meshes[0], axis="sites", eps=EPS)
        build(single)
        router = _make_router(meshes, axis="sites", max_batch=1024)
        build(router)
        ph.report["rows"] = 2 * sum(len(rows) for _, rows in feed)

        placement = router.placement()
        owners = {router.cell(c).pipeline.mesh.devices.flat[0] for c in placement.values()}
        ph.report["cells_used"] = len(set(placement.values()))
        if owners != set(devices[:4]):
            raise AssertionError(f"tenants landed on {len(owners)} of the 4 chips")
        for name in mats:
            cell = router.cell(placement[name])
            state_devs = _device_set(cell.pipeline.tracker(name).state)
            if state_devs != {cell.pipeline.mesh.devices.flat[0]}:
                raise AssertionError(f"{name}: state on {state_devs}, not its cell's chip")

        rng = np.random.default_rng(seed + 2)
        queries = [(name, _unit_directions(rng, sizes.queries, d)) for name in mats]
        queries += [("hh", np.arange(64, dtype=np.float32)[:, None]),
                    ("quantile", np.stack([rank_query(v) for v in np.linspace(1, 60, 64)])),
                    ("leverage", np.stack([subspace_query(v) for v in
                                           _unit_directions(rng, 64, d)]))]
        base = single.engine.query_packed([PackedRequest(t, q) for t, q in queries])
        spread = router.query_batch(queries)
        for (tenant, _), b, g in zip(queries, base, spread):
            b, g = b.estimates, g.estimates
            if b.dtype != g.dtype or b.tobytes() != g.tobytes():
                raise AssertionError(f"{tenant}: 4-cell answers differ from one pipeline")
        ph.report["byte_identical_tenants"] = len(queries)
        for (name, x), res in zip(queries[: len(mats)], spread):
            gram = _gram(mats[name])
            _check_matrix(ph, name, res.estimates, gram, x, EPS * np.trace(gram))
        router.close()
        single.close()
    return ph.report


# -- entry point ------------------------------------------------------------------


def run_phases(devices, *, chips: int = 1, seed: int = 0, sizes: Sizes = Sizes()) -> list[dict]:
    """Run the one-chip phases, or with ``chips=4`` the four-chip ones, on
    ``devices``; returns their report lines.  Raises on any failed check."""
    cuts = sizes.cuts()
    if cuts:
        print(json.dumps({"cut": {k: [list(np.atleast_1d(v)) for v in fv]
                                  for k, fv in cuts.items()}}, default=int), flush=True)
    phases = ((phase_sites_mesh, phase_router_cells) if chips == 4
              else (phase_paper_streams, phase_fleet))
    with CompileMeter() as meter:
        return [phase(meter, devices, sizes, seed) for phase in phases]


def main(argv=None) -> int:
    """Parse arguments, refuse a machine without a TPU, run and report."""
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": str(cache_dir)}), flush=True)
    reports = run_phases(devices, chips=args.chips, seed=args.seed)
    missing = [f"{r['phase']}/{k}" for r in reports
               for k, v in r["tpu_custom_call"].items() if not v]
    if missing:
        raise AssertionError(f"no Pallas TPU kernel in the compiled program of {missing}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
