"""Time as a first-class dimension: windowed + decayed protocol properties.

The tentpole contract: every protocol kind (matrix, hh, quantile,
leverage) gains a sliding-window and an exponential-decay flavor built by
folding the existing merge identities over per-bucket jit states
(``core/windows.py``), registered as ordinary ``(kind, engine, name)``
specs.  This file pins the three properties the wrappers must satisfy:

  * a windowed answer equals a fresh sketch fed only the in-window rows,
    within the kind's eps envelope — for all four kinds, both engines,
    and across a ``state_payload``/``restore_payload`` round trip;
  * arrival order does not matter: timestamp-shuffled ingest within the
    lateness bound is byte-identical to the sorted run (bucket-merge
    order invariance), and late-beyond-watermark rows are shed with a
    counted typed error, never silently dropped or applied;
  * exponential decay matches the closed-form ``gamma^(T - t)`` weights
    against a float64 reference to 1e-5.

Property tests run under hypothesis when installed and as seeded sweeps
otherwise (``conftest.run_property``) — never skipped.
"""
import jax
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
except ModuleNotFoundError:  # tier-1 runs on minimal installs too
    st = None

from conftest import run_property
from repro.core.windows import LateRowError, TimedRows, WatermarkTracker
from repro.runtime.policies import EveryKSteps, OnWindowClose
from repro.runtime.pipeline import StreamingPipeline
from repro.runtime.registry import create_protocol, specs

KINDS = ("matrix", "hh", "quantile", "leverage")
D = 8
EPS = 0.25
WINDOW, BUCKETS = 16.0, 4  # bucket width 4.0
M = 4  # paper sites for the event engine


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_cache():
    """This module runs last in a full tier-1 sweep, after ~400 tests'
    compiled executables have piled up in-process; XLA's single-core JIT
    has been seen segfaulting on the next compile under that load.
    Dropping the cache here costs a few recompiles and buys stability."""
    jax.clear_caches()


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def _make(kind, engine, mode, mesh=None, **kw):
    """One windowed/decayed protocol through the public registry path."""
    name = ("P2" if kind == "matrix" else "P1") + mode
    base = dict(eps=EPS)
    if kind in ("matrix", "leverage"):
        base["d"] = D
    if engine == "shard":
        base["mesh"] = mesh
    else:
        base["m"] = M
        base.setdefault("sites", 2)  # exercise the per-site round-robin
    base.update(kw)
    return create_protocol(name, engine=engine, kind=kind, **base)


def _batch(kind, rng, n=8):
    if kind in ("matrix", "leverage"):
        return rng.normal(size=(n, D)).astype(np.float32)
    if kind == "hh":
        return np.stack(
            [rng.integers(0, 32, n), rng.uniform(0.5, 2.0, n)], axis=1
        ).astype(np.float64)
    return np.stack(
        [rng.normal(size=n) * 5.0, np.ones(n)], axis=1
    ).astype(np.float64)


def _seeds(n):
    return [{"seed": s} for s in range(n)]


def _given_seed():
    return {"seed": st.integers(0, 2**16)}


def test_all_sixteen_windowed_specs_are_registered():
    """(4 kinds) x (win, decay) x (event, shard) land in the registry."""
    found = {
        (s.kind, s.engine, s.name)
        for s in specs()
        if s.name.endswith(("win", "decay"))
    }
    want = {
        (kind, engine, ("P2" if kind == "matrix" else "P1") + suffix)
        for kind in KINDS
        for engine in ("event", "shard")
        for suffix in ("win", "decay")
    }
    assert want <= found


# ---------------------------------------------------------------------------
# Property 1: windowed answer == fresh sketch over in-window rows (eps env.)
# ---------------------------------------------------------------------------


def _envelope_check(kind, proto, kept_rows, rng):
    """Served answer vs the exact in-window stream, per-kind eps envelope."""
    if kind == "matrix":
        frob = float(np.sum(kept_rows.astype(np.float64) ** 2))
        x = rng.normal(size=D)
        x = (x / np.linalg.norm(x)).astype(np.float32)
        exact = float(np.sum((kept_rows.astype(np.float64) @ x) ** 2))
        est = float(proto.query(x))
        slack = 1e-3 * frob + 1e-4
        assert exact - est >= -slack
        assert exact - est <= EPS * frob + slack
        assert proto.frob_estimate() == pytest.approx(frob, rel=1e-4)
    elif kind == "hh":
        w_tot = float(kept_rows[:, 1].sum())
        exact = {}
        for key, w in kept_rows:
            exact[int(key)] = exact.get(int(key), 0.0) + float(w)
        est = proto.estimates()
        assert proto.total_weight() == pytest.approx(w_tot, rel=1e-5)
        for key in set(exact) | set(est):
            err = abs(est.get(key, 0.0) - exact.get(key, 0.0))
            assert err <= EPS * w_tot + 1e-6
    elif kind == "quantile":
        w_tot = float(kept_rows[:, 1].sum())
        assert proto.total_weight() == pytest.approx(w_tot, rel=1e-5)
        probes = np.quantile(kept_rows[:, 0], [0.1, 0.5, 0.9])
        exact = np.array(
            [kept_rows[kept_rows[:, 0] <= v, 1].sum() for v in probes]
        )
        est = proto.rank(probes)
        assert np.all(np.abs(est - exact) <= EPS * w_tot + 1e-6)
    else:  # leverage
        frob = float(np.sum(kept_rows.astype(np.float64) ** 2))
        x = rng.normal(size=D)
        x = x / np.linalg.norm(x)
        exact = float(np.sum((kept_rows.astype(np.float64) @ x) ** 2))
        tab = proto.sampled_rows().astype(np.float64)
        rows, weights = tab[:, :D], tab[:, D + 1]
        est = float(np.sum(weights * (rows @ x) ** 2))
        slack = 1e-3 * frob + 1e-4
        assert exact - est >= -slack  # never overcounts mass
        assert exact - est <= 1.5 * EPS * frob + slack
        assert proto.total_weight() == pytest.approx(frob, rel=1e-4)


@pytest.mark.parametrize("engine", ("event", "shard"))
@pytest.mark.parametrize("kind", KINDS)
def test_windowed_answer_matches_fresh_inwindow_sketch(kind, engine, mesh):
    """Sliding window == fresh sketch fed only in-window rows, within the
    kind's eps envelope — including a checkpoint round trip mid-stream.

    The stream uses integer timestamps aligned to the bucket grid, so the
    retained-bucket set is exactly ``ts >= watermark - WINDOW`` and the
    reference stream is unambiguous.
    """

    def check(seed):
        rng = np.random.default_rng(seed)
        # T chosen so (T-1) - WINDOW lands on a bucket edge: retained rows
        # are exactly those with ts >= T-1-WINDOW.
        total = 29
        batches = [(float(t), _batch(kind, rng)) for t in range(total)]
        proto = _make(kind, engine, "win", mesh=mesh,
                      window=WINDOW, buckets=BUCKETS)
        for i, (ts, rows) in enumerate(batches):
            proto.step(rows, ts=ts)
            if i == total // 2:
                # checkpoint round trip mid-stream: the restored protocol
                # must continue (and answer) bit-identically
                arrays, meta = proto.state_payload()
                restored = _make(kind, engine, "win", mesh=mesh,
                                 window=WINDOW, buckets=BUCKETS)
                restored.restore_payload(arrays, meta)
                proto = restored
        cutoff = (total - 1) - WINDOW
        kept = np.concatenate(
            [rows for ts, rows in batches if ts >= cutoff]
        ).astype(np.float64)
        _envelope_check(kind, proto, kept, rng)
        # the window actually dropped something (the property isn't vacuous)
        assert proto.rows_seen > kept.shape[0]

    run_property(
        check,
        given=None if st is None else _given_seed,
        cases=_seeds(3),
        max_examples=10,
    )


@pytest.mark.parametrize("engine", ("event", "shard"))
@pytest.mark.parametrize("kind", KINDS)
def test_checkpoint_round_trip_is_bit_identical(kind, engine, mesh):
    """state_payload -> restore_payload reproduces arrays, counters, and
    subsequent answers bit-for-bit, pending out-of-order batches included."""
    rng = np.random.default_rng(11)
    proto = _make(kind, engine, "win", mesh=mesh,
                  window=WINDOW, buckets=BUCKETS, lateness=6.0)
    for ts in (0.0, 1.0, 4.0, 3.0, 9.0, 7.0):  # leaves batches pending
        proto.step(_batch(kind, rng), ts=ts)
    arrays, meta = proto.state_payload()
    restored = _make(kind, engine, "win", mesh=mesh,
                     window=WINDOW, buckets=BUCKETS, lateness=6.0)
    restored.restore_payload(arrays, meta)
    a2, m2 = restored.state_payload()
    assert meta == m2
    assert sorted(arrays) == sorted(a2)
    for k in arrays:
        np.testing.assert_array_equal(np.asarray(arrays[k]), np.asarray(a2[k]))
    # continues identically: same late shed, same drained state
    tail = _batch(kind, rng)
    for p in (proto, restored):
        p.step(tail, ts=20.0)
        p.advance(40.0)
    for (k, v), (k2, v2) in zip(
        sorted(proto.state_payload()[0].items()),
        sorted(restored.state_payload()[0].items()),
    ):
        assert k == k2
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
    # config mismatch is rejected, not silently absorbed
    other = _make(kind, engine, "win", mesh=mesh, window=WINDOW, buckets=2)
    with pytest.raises(ValueError, match="mismatch"):
        other.restore_payload(arrays, meta)


# ---------------------------------------------------------------------------
# Property 2: arrival order / watermark semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_shuffled_arrival_within_lateness_is_byte_identical(kind):
    """Batches with distinct timestamps drain in event-time order: any
    arrival shuffle inside the lateness bound yields byte-identical state
    and answers to the sorted run."""

    def check(seed):
        rng = np.random.default_rng(seed)
        batches = [(float(t), _batch(kind, rng)) for t in range(20)]
        a = _make(kind, "event", "win", window=8.0, buckets=4, lateness=100.0)
        b = _make(kind, "event", "win", window=8.0, buckets=4, lateness=100.0)
        for ts, rows in batches:
            a.step(rows, ts=ts)
        for i in rng.permutation(len(batches)):
            ts, rows = batches[i]
            b.step(rows, ts=ts)
        for p in (a, b):
            p.advance(200.0)  # watermark passes every batch: full drain
        (arr_a, meta_a), (arr_b, meta_b) = a.state_payload(), b.state_payload()
        # `closed` counts boundary crossings *observed since construction* —
        # a publish-cadence counter, order-dependent by design.  Everything
        # that describes sketch content must be identical.
        meta_a.pop("closed"), meta_b.pop("closed")
        assert meta_a == meta_b
        assert sorted(arr_a) == sorted(arr_b)
        for k in arr_a:
            np.testing.assert_array_equal(np.asarray(arr_a[k]), np.asarray(arr_b[k]))

    run_property(
        check,
        given=None if st is None else _given_seed,
        cases=_seeds(3),
        max_examples=10,
    )


@pytest.mark.parametrize("kind", ("hh", "quantile"))
def test_second_windowed_instance_compiles_nothing(kind):
    """The HH and quantile state algebra is compiled once per static
    configuration: a second protocol of the same spec, fed the same batch
    shapes, adds no entry to any jitted entry point's cache."""
    from repro.core import hh, quantiles

    entry_points = {
        "hh": (hh.mg_update_stream,),
        "quantile": (quantiles.quant_merge, quantiles._quant_absorb),
    }[kind]

    def run_one():
        rng = np.random.default_rng(0)
        proto = _make(kind, "event", "win", window=WINDOW, buckets=BUCKETS)
        for t in range(29):
            proto.step(_batch(kind, rng), ts=float(t))
        # the served answer folds the live buckets with the kind's merge
        answer = proto.estimates() if kind == "hh" else proto.rank([0.0])
        return proto.state_payload(), answer

    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    first = run_one()
    sizes = [fn._cache_size() for fn in entry_points]
    assert all(n > 0 for n in sizes)  # the first instance went through them
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        second = run_one()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert [fn._cache_size() for fn in entry_points] == sizes
    assert compiles == []
    (arrays_1, _), answer_1 = first
    (arrays_2, _), answer_2 = second
    for k in arrays_1:
        np.testing.assert_array_equal(np.asarray(arrays_1[k]), np.asarray(arrays_2[k]))
    np.testing.assert_equal(answer_1, answer_2)


@pytest.mark.parametrize("kind", KINDS)
def test_late_rows_are_shed_counted_and_never_applied(kind):
    """A batch older than the watermark raises ``LateRowError`` carrying
    the row count, increments the shed counters, and leaves state as if
    the batch never arrived — shed-and-report, not silent drop."""
    rng = np.random.default_rng(5)
    proto = _make(kind, "event", "win", window=8.0, buckets=4, lateness=2.0)
    for ts in (0.0, 5.0, 10.0):
        proto.step(_batch(kind, rng), ts=ts)
    before, meta_before = proto.state_payload()
    late = _batch(kind, rng)
    with pytest.raises(LateRowError) as err:
        proto.step(late, ts=3.0)  # watermark is 10 - 2 = 8
    assert err.value.n_rows == late.shape[0]
    assert err.value.watermark == pytest.approx(8.0)
    assert proto.late_batches == 1
    assert proto.late_rows == late.shape[0]
    after, meta_after = proto.state_payload()
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k]), np.asarray(after[k]))
    assert meta_after["applied_batches"] == meta_before["applied_batches"]
    # rows at exactly the watermark are NOT late (strict inequality)
    proto.step(_batch(kind, rng), ts=8.0)
    assert proto.late_batches == 1


def test_watermark_tracker_semantics():
    """watermark = max event time - lateness; lateness is strict."""
    wm = WatermarkTracker(lateness=3.0)
    assert wm.watermark == float("-inf")
    wm.observe(10.0)
    assert wm.watermark == 7.0
    wm.observe(5.0)  # max_ts is monotone
    assert wm.watermark == 7.0
    assert wm.is_late(6.9) and not wm.is_late(7.0)
    with pytest.raises(ValueError):
        WatermarkTracker(lateness=-1.0)


# ---------------------------------------------------------------------------
# Property 3: exponential decay matches the closed-form weights
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_decay_matches_closed_form_reference(kind):
    """With capacities large enough that no shrink fires, every decayed
    answer equals the float64 closed form ``sum_t gamma^(T-t) f(rows_t)``
    to 1e-5 relative."""

    def check(seed):
        rng = np.random.default_rng(seed)
        gamma, total = 0.9, 12
        big = {"matrix": {"l": 128}, "hh": {"k": 128},
               "quantile": {}, "leverage": {"cap": 256}}[kind]
        proto = _make(kind, "event", "decay", gamma=gamma, sites=1, **big)
        batches = [(float(t), _batch(kind, rng)) for t in range(total)]
        for ts, rows in batches:
            proto.step(rows, ts=ts)
        t_ref = batches[-1][0]
        w = {ts: gamma ** (t_ref - ts) for ts, _ in batches}
        if kind == "matrix":
            x = rng.normal(size=D)
            x = x / np.linalg.norm(x)
            want_q = sum(
                w[ts] * float(np.sum((rows.astype(np.float64) @ x) ** 2))
                for ts, rows in batches
            )
            want_f = sum(
                w[ts] * float(np.sum(rows.astype(np.float64) ** 2))
                for ts, rows in batches
            )
            assert float(proto.query(x.astype(np.float32))) == pytest.approx(
                want_q, rel=1e-5
            )
            assert proto.frob_estimate() == pytest.approx(want_f, rel=1e-5)
        elif kind == "hh":
            want = {}
            for ts, rows in batches:
                for key, wt in rows:
                    want[int(key)] = want.get(int(key), 0.0) + w[ts] * float(wt)
            est = proto.estimates()
            for key, val in want.items():
                assert est.get(key, 0.0) == pytest.approx(val, rel=1e-5)
            assert proto.total_weight() == pytest.approx(
                sum(want.values()), rel=1e-5
            )
        elif kind == "quantile":
            want = sum(w[ts] * float(rows[:, 1].sum()) for ts, rows in batches)
            assert proto.total_weight() == pytest.approx(want, rel=1e-5)
        else:  # leverage
            want_m = sum(
                w[ts] * float(np.sum(rows.astype(np.float64) ** 2))
                for ts, rows in batches
            )
            assert proto.total_weight() == pytest.approx(want_m, rel=1e-5)
            x = rng.normal(size=D)
            x = x / np.linalg.norm(x)
            want_q = sum(
                w[ts] * float(np.sum((rows.astype(np.float64) @ x) ** 2))
                for ts, rows in batches
            )
            tab = proto.sampled_rows().astype(np.float64)
            est = float(np.sum(tab[:, D + 1] * (tab[:, :D] @ x) ** 2))
            assert est == pytest.approx(want_q, rel=1e-5)

    run_property(
        check,
        given=None if st is None else _given_seed,
        cases=_seeds(3),
        max_examples=10,
    )


def test_decay_half_life_parameterization():
    """half_life is sugar for gamma = 2**(-1/half_life): mass halves."""
    rng = np.random.default_rng(3)
    proto = _make("quantile", "event", "decay", half_life=4.0, sites=1)
    rows = np.stack([rng.normal(size=16), np.ones(16)], 1)
    proto.step(rows, ts=0.0)
    w0 = proto.total_weight()
    proto.advance(4.0)
    proto.step(rows[:0], ts=4.0)  # empty batch: pure time advance
    # decay applies on the next real insert; force it with a tiny batch
    proto.step(np.array([[0.0, 0.0]]), ts=4.0)
    assert proto.total_weight() == pytest.approx(w0 / 2.0, rel=1e-5)
    with pytest.raises(ValueError):
        _make("quantile", "event", "decay", gamma=0.9, half_life=4.0)


# ---------------------------------------------------------------------------
# Runtime integration: OnWindowClose, published_at, gauges, packed serving
# ---------------------------------------------------------------------------


def test_pipeline_on_window_close_publishes_per_bucket_edge(mesh):
    """OnWindowClose fires exactly when a bucket boundary passes the
    watermark; published_at rides the event-time watermark and as_of
    time-travels to the version live at that instant."""
    rng = np.random.default_rng(0)
    pipe = StreamingPipeline(mesh, eps=EPS)
    pipe.add_windowed_tenant(
        "w", kind="matrix", d=D, window=8.0, buckets=4, policy=OnWindowClose()
    )
    published = []
    for t in range(20):
        snap = pipe.ingest("w", _batch("matrix", rng), ts=float(t))
        if snap is not None:
            published.append((float(t), snap))
    proto = pipe.tracker("w")
    assert len(published) == proto.windows_closed() > 0
    for ts, snap in published:
        assert snap.published_at == ts  # the watermark at publish time
        assert snap.meta["workload"] == "matrix"  # rides the matrix sweeps
        assert snap.meta["windowed"] is True
        assert pipe.store.as_of("w", snap.published_at).version == snap.version
    # between two edges, as_of pins the older version
    (t0, s0), (t1, s1) = published[0], published[1]
    assert pipe.store.as_of("w", (t0 + t1) / 2.0).version == s0.version
    # windowed snapshots serve through the ordinary packed sweep
    x = np.ones(D, np.float32) / np.sqrt(D)
    ticket = pipe.submit("w", x)
    pipe.flush()
    assert ticket.version == published[-1][1].version
    want = float(np.sum((pipe.store.get("w").matrix.astype(np.float64) @ x) ** 2))
    assert ticket.estimate == pytest.approx(want, rel=1e-4)
    pipe.close()


def test_pipeline_sheds_late_rows_with_counter(mesh):
    """Pipeline-level shed path: LateRowError propagates AND the shared
    late_rows ingest counter accounts for every shed row."""
    rng = np.random.default_rng(1)
    pipe = StreamingPipeline(mesh, eps=EPS)
    pipe.add_windowed_tenant(
        "w", kind="hh", window=8.0, buckets=4, lateness=1.0,
        policy=EveryKSteps(1),
    )
    pipe.ingest("w", _batch("hh", rng), ts=10.0)
    late = _batch("hh", rng)
    with pytest.raises(LateRowError):
        pipe.ingest("w", late, ts=2.0)
    assert pipe.stats()["late_rows"] == late.shape[0]
    # gauge exists for windowed tenants and tracks event-time lag
    payload = pipe.obs.registry.to_json()
    assert "repro_tenant_window_lag" in payload
    # TimedRows and ts= are the same wire format
    pipe.ingest("w", TimedRows(_batch("hh", rng), 11.0))
    assert pipe.tracker("w").watermark() == 10.0
    pipe.close()


def test_ingest_many_threads_event_time_serially(mesh):
    """(tenant, rows, ts) triples take the serial path and land in the
    same state as one-by-one timed ingest; late batches in a wave are
    counted-and-skipped, not wave-aborting."""
    rng = np.random.default_rng(2)
    mk = lambda: _batch("quantile", rng)
    batches = [(float(t), mk()) for t in range(8)]
    a = StreamingPipeline(mesh, eps=EPS)
    b = StreamingPipeline(mesh, eps=EPS)
    for pipe in (a, b):
        pipe.add_windowed_tenant(
            "q", kind="quantile", window=100.0, policy=EveryKSteps(1)
        )
    for ts, rows in batches:
        a.ingest("q", rows, ts=ts)
    b.ingest_many([("q", rows, ts) for ts, rows in batches])
    (arr_a, meta_a) = a._tenants["q"].adapter.state_payload()
    (arr_b, meta_b) = b._tenants["q"].adapter.state_payload()
    assert meta_a == meta_b
    for k in arr_a:
        np.testing.assert_array_equal(np.asarray(arr_a[k]), np.asarray(arr_b[k]))
    # a late batch inside a wave is shed (counted) while the wave proceeds
    before = b.stats()["late_rows"]
    late = mk()
    n = b.ingest_many([("q", late, 0.0), ("q", mk(), 9.0)])
    assert b.stats()["late_rows"] == before + late.shape[0]
    assert n >= 1  # the in-time batch still published
    a.close(), b.close()
