"""Query-serving subsystem: store versioning, engine paths, cache, service.

Covers the PR acceptance gate: a 1024-direction batch served end-to-end,
with the Pallas path bit-for-bit equal to the reference under interpret
mode and every estimate inside the paper's ``eps ||A||_F^2`` envelope.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fd import fd_init, fd_matrix, fd_update_stream
from repro.kernels.ops import quadform
from repro.kernels.ref import ref_quadform
from repro.query import QueryEngine, QueryService, SketchStore

EPS = 0.1
D = 256  # <= one quadform d-block, so the Pallas path is bit-exact vs ref


def _lowrank(rng, n, d, rank=8, noise=0.05):
    u = rng.normal(size=(n, rank)) * (np.arange(rank, 0, -1) ** 2)
    return (u @ rng.normal(size=(rank, d)) + noise * rng.normal(size=(n, d))).astype(
        np.float32
    )


@pytest.fixture(scope="module")
def published():
    """(store, A, frob, snapshot) for an FD sketch of a synthetic stream."""
    rng = np.random.default_rng(7)
    a = _lowrank(rng, 20000, D)
    l = int(np.ceil(4.0 / EPS))
    st = fd_update_stream(fd_init(l, D), jnp.asarray(a))
    frob = float(np.sum(a.astype(np.float64) ** 2))
    store = SketchStore()
    snap = store.publish(
        "run", np.asarray(fd_matrix(st)), frob=frob, eps=EPS,
        delta_sum=float(st.delta_sum), n_seen=a.shape[0],
    )
    return store, a, frob, snap


def _unit_directions(rng, n, d):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


def test_store_versions_are_monotonic_immutable(rng):
    store = SketchStore()
    b = rng.normal(size=(4, 8)).astype(np.float32)
    s1 = store.publish("t", b, frob=1.0, eps=0.5)
    s2 = store.publish("t", 2 * b, frob=4.0, eps=0.5)
    s_other = store.publish("u", b, frob=1.0, eps=0.5)
    assert (s1.version, s2.version) == (1, 2)
    assert s_other.version == 1  # tenant namespaces are independent
    assert store.latest_version("t") == 2
    assert store.versions("t") == [1, 2]
    assert store.tenants() == ["t", "u"]
    # latest vs pinned
    np.testing.assert_array_equal(store.get("t").matrix, s2.matrix)
    np.testing.assert_array_equal(store.get("t", version=1).matrix, b)
    # published snapshots are frozen and decoupled from the caller's buffer
    with pytest.raises(ValueError):
        store.get("t", 1).matrix[0, 0] = 99.0
    b[0, 0] = -1.0
    assert store.get("t", 1).matrix[0, 0] != -1.0
    with pytest.raises(KeyError):
        store.get("t", version=5)
    with pytest.raises(KeyError):
        store.get("nobody")


def test_store_retention_prunes_old_versions(rng):
    store = SketchStore(retain=2)
    b = rng.normal(size=(2, 4)).astype(np.float32)
    for _ in range(5):
        store.publish("t", b, frob=1.0, eps=0.5)
    assert store.versions("t") == [4, 5]  # numbering keeps advancing
    with pytest.raises(KeyError):
        store.get("t", version=1)


def test_snapshot_error_bound_prefers_instance_bound(rng):
    store = SketchStore()
    b = rng.normal(size=(2, 4)).astype(np.float32)
    tight = store.publish("t", b, frob=100.0, eps=0.1, delta_sum=3.0)
    worst = store.publish("t", b, frob=100.0, eps=0.1)
    assert tight.error_bound == pytest.approx(3.0)
    assert worst.error_bound == pytest.approx(10.0)  # eps * ||A||_F^2


# ---------------------------------------------------------------------------
# engine: parity + paper bound + cache
# ---------------------------------------------------------------------------


def test_all_paths_agree_and_satisfy_paper_bound(published):
    store, a, frob, snap = published
    rng = np.random.default_rng(1)
    x = _unit_directions(rng, 64, D)
    truth = np.sum((a.astype(np.float64) @ x.T.astype(np.float64)) ** 2, axis=0)
    engine = QueryEngine(store)
    fp_slack = 1e-4 * frob  # f32 accumulation noise, same convention as test_fd
    results = {}
    for path in ("pallas", "cached", "naive"):
        res = engine.query_batch(x, tenant="run", path=path)
        results[path] = res.estimates
        gap = truth - res.estimates.astype(np.float64)
        # paper guarantee: 0 <= ||Ax||^2 - ||Bx||^2 <= delta_sum <= eps ||A||_F^2
        assert res.error_bound <= EPS * frob
        assert np.all(gap <= res.error_bound + fp_slack)
        assert np.all(gap >= -fp_slack)
    np.testing.assert_allclose(results["pallas"], results["cached"], rtol=1e-5)
    np.testing.assert_allclose(results["cached"], results["naive"], rtol=1e-5)


def test_engine_serves_1024_direction_batch_bitexact_vs_ref(published):
    """Acceptance gate: 1024 directions end-to-end, Pallas == ref to 1e-5."""
    store, a, frob, snap = published
    rng = np.random.default_rng(2)
    x = _unit_directions(rng, 1024, D)
    engine = QueryEngine(store, interpret=True)
    res = engine.query_batch(x, tenant="run", path="pallas")
    want = np.asarray(ref_quadform(jnp.asarray(snap.matrix), jnp.asarray(x)))
    # The kernel sums its d-blocks in another order than XLA's matmul, so
    # the f32 results differ in the last bits; ops.py documents 1e-5.
    np.testing.assert_allclose(res.estimates, want, rtol=1e-5)
    # and the whole batch stays inside the eps envelope vs the dense truth
    truth = np.sum((a.astype(np.float64) @ x.T.astype(np.float64)) ** 2, axis=0)
    gap = truth - res.estimates.astype(np.float64)
    assert np.all(np.abs(gap) <= EPS * frob)


def test_spectrum_cache_hits_and_version_invalidation(published):
    store, a, frob, snap = published
    rng = np.random.default_rng(3)
    x = _unit_directions(rng, 8, D)
    engine = QueryEngine(store)
    engine.query_batch(x, tenant="run", path="cached")
    stats = engine.cache_stats()
    assert (stats["hits"], stats["misses"], stats["entries"]) == (0, 1, 1)
    assert stats["spectrum"] == {"hits": 0, "misses": 1, "evictions": 0}
    engine.query_batch(x, tenant="run", path="cached")
    engine.top_directions(4, tenant="run")
    engine.stable_rank(tenant="run")
    stats = engine.cache_stats()
    assert (stats["hits"], stats["misses"], stats["entries"]) == (3, 1, 1)
    assert stats["hit_rate"] == 0.75
    assert stats["factor"] == {"hits": 0, "misses": 0, "evictions": 0}
    # a new version is a new cache key: the old entry can never be served
    v2 = store.publish("run", snap.matrix * 2.0, frob=4 * frob, eps=EPS)
    res = engine.query_batch(x, tenant="run", path="cached")
    assert res.version == v2.version
    assert engine.cache_stats()["misses"] == 2
    np.testing.assert_allclose(
        res.estimates,
        4.0 * engine.query_batch(x, tenant="run", version=snap.version, path="cached").estimates,
        rtol=1e-5,
    )


def test_spectrum_cache_lru_eviction(rng):
    store = SketchStore()
    b = rng.normal(size=(4, 16)).astype(np.float32)
    for _ in range(3):
        store.publish("t", b, frob=1.0, eps=0.5)
    engine = QueryEngine(store, cache_size=2)
    for v in (1, 2, 3, 1):
        engine.spectrum("t", v)
    # v1 was evicted by v3 and had to be refactored — and the evictions
    # are *counted* (a thrashing cache must not look healthy)
    stats = engine.cache_stats()
    assert (stats["hits"], stats["misses"], stats["entries"]) == (0, 4, 2)
    assert stats["spectrum"]["evictions"] == 2
    assert stats["evictions"] == 2


def test_top_directions_match_dense_pca(published):
    store, a, frob, snap = published
    engine = QueryEngine(store)
    vt_k, s_k = engine.top_directions(2, tenant="run")
    _, _, vt = np.linalg.svd(a.astype(np.float64), full_matrices=False)
    for i in range(2):
        assert abs(float(vt_k[i] @ vt[i])) > 0.99


# ---------------------------------------------------------------------------
# kernel wrapper: ragged batches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("l,d,n", [(17, 300, 37), (40, 256, 1000), (8, 128, 1), (3, 9, 5)])
def test_quadform_ragged_padding(l, d, n):
    rng = np.random.default_rng(l + d + n)
    b = jnp.asarray(rng.normal(size=(l, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    got = np.asarray(quadform(b, x))
    want = np.asarray(ref_quadform(b, x))
    assert got.shape == (n,)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4 * d)


# ---------------------------------------------------------------------------
# service: admission, coalescing, padding correctness
# ---------------------------------------------------------------------------


def test_service_coalesces_and_resolves_tickets(published):
    store, a, frob, snap = published
    rng = np.random.default_rng(4)
    x = _unit_directions(rng, 300, D)
    engine = QueryEngine(store)
    svc = QueryService(engine, tenant="run", max_batch=256, auto_flush=True)
    tickets = [svc.submit(row) for row in x]
    assert svc.pending() == 300 - 256  # one auto-flush fired at max_batch
    svc.flush()
    assert svc.pending() == 0
    want = engine.query_batch(x, tenant="run", path="pallas").estimates
    got = np.array([t.result()[0] for t in tickets], np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    stats = svc.stats()
    assert stats.queries == 300 and stats.batches == 2
    # ragged tail of 44 was padded up to the 64 bucket
    assert stats.padded == 64 - 44
    assert stats.queries_per_sec > 0


def test_service_ticket_result_triggers_flush(published):
    store, a, frob, snap = published
    rng = np.random.default_rng(5)
    engine = QueryEngine(store)
    svc = QueryService(engine, tenant="run", max_batch=64, path="cached")
    x = _unit_directions(rng, 3, D)
    tickets = [svc.submit(row) for row in x]
    est, bound, version = tickets[1].result()  # implicit flush
    assert tickets[0].done and tickets[2].done
    assert version == store.latest_version("run")
    assert bound == store.get("run").error_bound
    assert est == pytest.approx(engine.query(x[1], tenant="run", path="cached"), rel=1e-6)


def test_service_rejects_bad_shapes(published):
    store, *_ = published
    svc = QueryService(QueryEngine(store), tenant="run")
    with pytest.raises(ValueError):
        svc.submit(np.zeros((2, D), np.float32))


def test_service_failed_flush_keeps_tickets_pending(published):
    store, *_ = published
    svc = QueryService(QueryEngine(store), tenant="unpublished", auto_flush=False)
    ticket = svc.submit(np.zeros(D, np.float32))
    with pytest.raises(KeyError):
        svc.flush()
    assert svc.pending() == 1 and not ticket.done
    # once the cause is fixed (tenant published), the same ticket resolves
    store.publish("unpublished", np.ones((2, D), np.float32), frob=1.0, eps=0.5)
    svc.flush()
    assert ticket.done


# ---------------------------------------------------------------------------
# tracker integration: publish() into the store
# ---------------------------------------------------------------------------


def test_tracker_publishes_versioned_snapshots(rng):
    from repro.core.tracker import DistributedMatrixTracker

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    d = 16
    tracker = DistributedMatrixTracker(mesh, d, eps=0.25, axis="data")
    a = _lowrank(np.random.default_rng(6), 2048, d, rank=4)
    for i in range(0, 2048, 256):
        tracker.update(jnp.asarray(a[i : i + 256]))
    store = SketchStore()
    s1 = tracker.publish(store, tenant="train")
    tracker.update(jnp.asarray(a[:256]))
    s2 = tracker.publish(store, tenant="train", meta={"step": 9})
    assert (s1.version, s2.version) == (1, 2)
    assert s1.meta["protocol"] == "P2"
    assert s2.meta["step"] == 9
    assert s1.frob > 0 and s1.eps == 0.25
    # the published snapshot answers queries consistently with the tracker
    engine = QueryEngine(store)
    x = np.zeros(d, np.float32)
    x[0] = 1.0
    assert engine.query(x, tenant="train") == pytest.approx(
        tracker.query(jnp.asarray(x)), rel=1e-5, abs=1e-4
    )
