"""Frequent Directions: paper guarantees, mergeability, JAX-vs-numpy parity."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property-based tests skip gracefully on minimal installs
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
except ModuleNotFoundError:
    hypothesis = None

from repro.core.fd import (
    FDSketch,
    fd_init,
    fd_matrix,
    fd_merge,
    fd_query,
    fd_route,
    fd_update_stream,
    jit_cache_stats,
)

SLACK = 1e-4  # fp slack on the exact-arithmetic bounds


def _lowrank(rng, n, d, rank=5, noise=0.05):
    u = rng.normal(size=(n, rank)) * (np.arange(rank, 0, -1) ** 2)
    return u @ rng.normal(size=(rank, d)) + noise * rng.normal(size=(n, d))


def test_fd_covariance_bound(rng):
    n, d, l = 3000, 32, 16
    a = _lowrank(rng, n, d)
    sk = FDSketch(l, d)
    sk.extend(a)
    err = sk.covariance_error(a)
    assert err <= 2.0 / l + SLACK
    # the instance-specific bound is tighter and must also hold
    assert err * np.sum(a * a) <= sk.delta_sum + SLACK * np.sum(a * a)


def test_fd_directional_invariant(rng):
    n, d, l = 2000, 24, 12
    a = _lowrank(rng, n, d)
    st_ = fd_update_stream(fd_init(l, d), jnp.asarray(a, jnp.float32))
    frob = float(np.sum(a * a))
    for _ in range(25):
        x = rng.normal(size=d)
        x /= np.linalg.norm(x)
        ax = float(np.sum((a @ x) ** 2))
        bx = float(fd_query(st_, jnp.asarray(x, jnp.float32)))
        # 0 <= ||Ax||^2 - ||Bx||^2 <= delta_sum   (paper Section 3)
        assert ax - bx >= -SLACK * frob
        assert ax - bx <= float(st_.delta_sum) + SLACK * frob


@pytest.mark.parametrize(
    "route, n, d, l, call",
    [
        ("covariance", 512, 16, 8, 128),  # four calls of 128 rows: one shrink each
        ("chain", 512, 12, 4, 8),  # 8-row calls: two l-row links each
    ],
)
def test_fd_jax_matches_numpy(rng, route, n, d, l, call):
    assert fd_route(call, l, d) == route
    a = _lowrank(rng, n, d).astype(np.float32)
    sk = FDSketch(l, d)
    st_ = fd_init(l, d)
    for i in range(0, n, call):
        if route == "covariance":
            sk.absorb_block(a[i : i + call])
        else:
            # numpy oracle consumes in l-row chunks to match the JAX chain
            for j in range(i, i + call, l):
                sk.extend(a[j : j + l])
                if sk.fill > l:
                    sk._shrink()
        st_ = fd_update_stream(st_, jnp.asarray(a[i : i + call]))
    ga = sk.matrix()[:l]
    gb = np.asarray(fd_matrix(st_))
    # sketches are equal up to sign/rotation: compare Gram matrices
    np.testing.assert_allclose(ga.T @ ga, gb.T @ gb, rtol=2e-3, atol=2e-2)


@pytest.mark.parametrize(
    "n, l, d, route",
    [
        (2000, 40, 90, "covariance"),  # a site's super-step on the MSD cells
        (160, 40, 90, "covariance"),  # the coordinator at m = 4
        (40, 40, 90, "chain"),  # the coordinator at m = 1: one 80 x 80 eigh
        (16, 8, 24, "chain"),
        (0, 8, 4, "chain"),  # nothing to absorb: an empty scan
    ],
)
def test_fd_route_rule(n, l, d, route):
    assert fd_route(n, l, d) == route


def test_fd_routes_are_counted_per_traced_signature(rng):
    l, d = 8, 23  # a width no other test traces, so this test sees its own
    before = jit_cache_stats()["routes"]
    st_ = fd_init(l, d)
    for _ in range(2):
        st_ = fd_update_stream(st_, jnp.asarray(rng.normal(size=(64, d)), jnp.float32))
        st_ = fd_update_stream(st_, jnp.asarray(rng.normal(size=(8, d)), jnp.float32))
    after = jit_cache_stats()["routes"]
    assert {k: after[k] - before[k] for k in after} == {"covariance": 1, "chain": 1}


def test_fd_covariance_route_guarantee(rng):
    n, d, l, call = 2000, 24, 8, 250
    assert fd_route(call, l, d) == "covariance"
    a = _lowrank(rng, n, d, rank=12, noise=0.5)
    st_ = fd_init(l, d)
    for i in range(0, n, call):
        st_ = fd_update_stream(st_, jnp.asarray(a[i : i + call], jnp.float32))
    frob = float(np.sum(a * a))
    delta = float(st_.delta_sum)
    assert delta > 0  # d > l: every shrink subtracts mass
    assert delta <= frob / (l + 1) + SLACK * frob
    b = np.asarray(fd_matrix(st_), np.float64)
    for _ in range(25):
        x = rng.normal(size=d)
        x /= np.linalg.norm(x)
        gap = float(np.sum((a @ x) ** 2)) - float(np.sum((b @ x) ** 2))
        # 0 <= ||Ax||^2 - ||Bx||^2 <= delta_sum   (paper Section 3)
        assert -SLACK * frob <= gap <= delta + SLACK * frob
    # The spectral form of the same bound: ||A^T A - B^T B||_2 <= delta_sum.
    assert np.linalg.norm(a.T @ a - b.T @ b, 2) <= delta + SLACK * frob
    assert float(st_.frob) == pytest.approx(frob, rel=1e-5)
    assert int(st_.n_seen) == n


def test_fd_covariance_route_rows_are_orthogonal_sorted_and_l_live(rng):
    n, d, l = 300, 24, 8
    a = _lowrank(rng, n, d, rank=12, noise=0.5).astype(np.float32)
    st_ = fd_update_stream(fd_init(l, d), jnp.asarray(a[:100]))
    st_ = fd_update_stream(st_, jnp.asarray(a[100:]))
    buf = np.asarray(st_.buf, np.float64)
    assert not np.any(buf[l:])  # rows past l are zero, exactly
    live = buf[:l]
    norms = np.sum(live * live, axis=1)
    assert np.all(np.diff(norms) <= 1e-6 * norms[0])  # sorted by norm, descending
    g = live @ live.T
    off = g - np.diag(np.diag(g))
    assert np.max(np.abs(off)) <= 1e-5 * norms[0]  # orthogonal rows


def test_fd_covariance_route_exact_when_d_at_most_l(rng):
    n, d, l = 500, 6, 8
    a = rng.normal(size=(n, d)).astype(np.float32)
    st_ = fd_init(l, d)
    for i in range(0, n, 100):
        st_ = fd_update_stream(st_, jnp.asarray(a[i : i + 100]))
    assert float(st_.delta_sum) == 0.0
    b = np.asarray(fd_matrix(st_), np.float64)
    assert not np.any(b[d:])  # d directions span everything: the rest stay zero
    ata = a.astype(np.float64).T @ a
    np.testing.assert_allclose(b.T @ b, ata, rtol=1e-5, atol=1e-4 * np.abs(ata).max())


def test_fd_covariance_route_zero_rows_are_free(rng):
    d, l = 16, 8
    a = _lowrank(rng, 64, d).astype(np.float32)
    padded = np.concatenate([a[:20], np.zeros((30, d), np.float32), a[20:]])
    assert fd_route(len(a), l, d) == fd_route(len(padded), l, d) == "covariance"
    st1 = fd_update_stream(fd_init(l, d), jnp.asarray(a))
    st2 = fd_update_stream(fd_init(l, d), jnp.asarray(padded))
    assert int(st2.n_seen) == int(st1.n_seen) == 64
    assert float(st2.frob) == pytest.approx(float(st1.frob), rel=1e-6)
    assert float(st2.delta_sum) == pytest.approx(float(st1.delta_sum), rel=1e-5)
    b1, b2 = np.asarray(fd_matrix(st1)), np.asarray(fd_matrix(st2))
    scale = float(np.sum(b1 * b1))
    np.testing.assert_allclose(b2.T @ b2, b1.T @ b1, atol=1e-5 * scale)


def test_fd_merge_error_adds(rng):
    n, d, l = 2000, 24, 16
    a = _lowrank(rng, n, d)
    st1 = fd_update_stream(fd_init(l, d), jnp.asarray(a[: n // 2], jnp.float32))
    st2 = fd_update_stream(fd_init(l, d), jnp.asarray(a[n // 2 :], jnp.float32))
    merged = fd_merge(st1, st2)
    b = np.asarray(fd_matrix(merged))
    err = np.linalg.norm(a.T @ a - b.T @ b, 2)
    assert err <= float(merged.delta_sum) + SLACK * np.sum(a * a)
    assert float(merged.frob) == pytest.approx(np.sum(a * a), rel=1e-3)
    assert int(merged.n_seen) == n


def test_fd_zero_rows_are_free(rng):
    d, l = 16, 8
    a = rng.normal(size=(64, d)).astype(np.float32)
    st1 = fd_update_stream(fd_init(l, d), jnp.asarray(a))
    padded = np.concatenate([a, np.zeros((40, d), np.float32)])
    st2 = fd_update_stream(fd_init(l, d), jnp.asarray(padded))
    assert int(st2.n_seen) == int(st1.n_seen)
    assert float(st2.frob) == pytest.approx(float(st1.frob), rel=1e-5)


def test_fd_property_invariant():
    """For arbitrary matrices: 0 <= ||Ax||^2 - ||Bx||^2 <= 2||A||_F^2 / l.

    Hypothesis when installed, else a seeded sweep over the same check.
    """
    from conftest import run_property

    def check(a, l):
        d = a.shape[1]
        st_ = fd_update_stream(fd_init(l, d), jnp.asarray(a))
        frob = float(np.sum(a.astype(np.float64) ** 2))
        x = np.ones(d) / np.sqrt(d)
        ax = float(np.sum((a @ x) ** 2))
        bx = float(fd_query(st_, jnp.asarray(x, jnp.float32)))
        slack = 1e-3 * frob + 1e-4
        assert ax - bx >= -slack
        assert ax - bx <= 2.0 * frob / l + slack

    rng = np.random.default_rng(0)

    def seeded():
        for _ in range(25):
            n, d = int(rng.integers(20, 61)), int(rng.integers(4, 11))
            a = rng.uniform(-5, 5, size=(n, d)).astype(np.float32)
            yield {"a": a, "l": int(rng.integers(3, 9))}

    run_property(
        check,
        given=lambda: {
            "a": hnp.arrays(
                np.float32,
                st.tuples(st.integers(20, 60), st.integers(4, 10)),
                elements=st.floats(-5, 5, width=32),
            ),
            "l": st.integers(3, 8),
        },
        cases=seeded(),
        max_examples=25,
    )


def test_pipeline_stats_carry_fd_routes(rng):
    """A P1 super-step of an MSD-cell shape: the site's 2,000 rows take the
    covariance route, the coordinator's one shipped l-row sketch the chain."""
    import jax

    from repro.runtime.pipeline import StreamingPipeline

    d = 91  # a width no other test traces, so this test sees its own signatures
    pipe = StreamingPipeline(jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",)),
                             eps=0.1, protocol="P1")
    pipe.add_tenant("t0", d)
    before = jit_cache_stats()["routes"]
    for _ in range(2):
        pipe.ingest("t0", rng.normal(size=(2000, d)).astype(np.float32))
    after = pipe.stats()["fd_routes"]
    pipe.close()
    assert after == jit_cache_stats()["routes"]
    assert {k: after[k] - before[k] for k in after} == {"covariance": 1, "chain": 1}
