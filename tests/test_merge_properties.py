"""Property tests for the four merge identities the cluster leans on.

``fd_merge`` / ``mg_merge`` / ``quant_merge`` / ``lev_merge`` are the
algebra behind every distributed path in this repo: protocol round
collection, tenant export/import, and — since the transport landed —
crash-restart replay, which silently assumes that re-merging a replayed
shard cannot change the served answer.  Three laws per kind:

  * commutativity up to the served answer — merge(a, b) and merge(b, a)
    may differ in representation (row order inside the shrink, tuple
    layout) but must serve the same answers within the certified band;
  * identity-element absorption — merging with the empty/all-pad state
    is a no-op (bit-identical where the representation is canonical);
  * merge-of-splits == merge-of-stream — a stream split across shards
    and merged serves within the same certified envelope as the
    unsplit stream, with mass/weight/count conservation *exact*.

Each law runs under hypothesis when installed and over a seeded numpy
sweep otherwise (see ``conftest.run_property``) — never skipped.
"""
import jax.numpy as jnp
import numpy as np
from conftest import run_property

from repro.core.fd import fd_init, fd_merge, fd_query, fd_update_stream
from repro.core.hh import mg_init, mg_items, mg_merge, mg_update_stream
from repro.core.leverage import lev_init, lev_merge, lev_merge_spill
from repro.core.quantiles import (
    exact_ranks,
    quant_band,
    quant_init,
    quant_insert,
    quant_merge,
    quant_table,
    table_rank,
)

try:  # hypothesis is a test extra; the seeded sweeps below cover its absence
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
except ModuleNotFoundError:
    st = None


# ---------------------------------------------------------------------------
# fd_merge
# ---------------------------------------------------------------------------


# Shapes draw from small fixed sets so jit-compiled helpers hit their
# shape caches — the value distribution stays wide, the compile count small.
_FD_NS, _FD_DS, _FD_L = (5, 17, 32), (4, 8), 4


def _fd_cases(n_cases):
    rng = np.random.default_rng(7)
    for _ in range(n_cases):
        d = int(rng.choice(_FD_DS))
        na, nb = int(rng.choice(_FD_NS)), int(rng.choice(_FD_NS))
        yield {
            "a": rng.normal(size=(na, d)).astype(np.float32),
            "b": rng.normal(size=(nb, d)).astype(np.float32),
            "l": _FD_L,
        }


def _fd_given():
    def mat():
        return hnp.arrays(
            np.float32,
            st.tuples(st.sampled_from(_FD_NS), st.shared(st.sampled_from(_FD_DS), key="d")),
            elements=st.floats(-3, 3, width=32),
        )

    return {"a": mat(), "b": mat(), "l": st.just(_FD_L)}


def test_fd_merge_commutes_up_to_served_answer():
    """merge(a,b) and merge(b,a) serve ||Bx||^2 within fp tolerance of
    each other and keep identical mass/count/error accounting."""

    def check(a, b, l):
        d = a.shape[1]
        sa = fd_update_stream(fd_init(l, d), jnp.asarray(a))
        sb = fd_update_stream(fd_init(l, d), jnp.asarray(b))
        ab, ba = fd_merge(sa, sb), fd_merge(sb, sa)
        assert float(ab.frob) == float(ba.frob)  # f32 addition commutes
        assert int(ab.n_seen) == int(ba.n_seen)
        frob = float(np.sum(a.astype(np.float64) ** 2) + np.sum(b.astype(np.float64) ** 2))
        x = jnp.eye(d, dtype=jnp.float32)  # all coordinate directions at once
        qa = np.asarray(fd_query(ab, x.T))
        qb = np.asarray(fd_query(ba, x.T))
        np.testing.assert_allclose(qa, qb, atol=1e-3 * max(frob, 1.0) + 1e-5)

    run_property(check, given=_fd_given, cases=_fd_cases(25), max_examples=25)


def test_fd_merge_identity_absorption():
    """Merging with the empty sketch changes nothing the query can see."""

    def check(a, b, l):
        del b
        d = a.shape[1]
        sa = fd_update_stream(fd_init(l, d), jnp.asarray(a))
        for merged in (fd_merge(sa, fd_init(l, d)), fd_merge(fd_init(l, d), sa)):
            assert float(merged.frob) == float(sa.frob)
            assert int(merged.n_seen) == int(sa.n_seen)
            x = jnp.eye(d, dtype=jnp.float32)
            qa = np.asarray(fd_query(sa, x.T))
            qm = np.asarray(fd_query(merged, x.T))
            np.testing.assert_allclose(
                qm, qa, atol=1e-4 * max(float(sa.frob), 1.0) + 1e-6
            )

    run_property(check, given=_fd_given, cases=_fd_cases(15), max_examples=15)


# All-zero inputs: zero rows are not counted in ``n_seen``.
_FD_ZERO_ROWS = {
    "a": np.zeros((5, 4), np.float32),
    "b": np.zeros((5, 4), np.float32),
    "l": _FD_L,
}


def test_fd_merge_of_splits_matches_stream_envelope():
    """Split-then-merge conserves mass/count exactly and keeps the FD
    guarantee ``0 <= ||Ax||^2 - ||Bx||^2 <= delta_sum`` of the full stream.
    ``n_seen`` counts the non-zero rows (``fd_update``'s contract)."""

    def check(a, b, l):
        d = a.shape[1]
        full = np.concatenate([a, b], axis=0)
        merged = fd_merge(
            fd_update_stream(fd_init(l, d), jnp.asarray(a)),
            fd_update_stream(fd_init(l, d), jnp.asarray(b)),
        )
        frob = float(np.sum(full.astype(np.float64) ** 2))
        n_nonzero = int(np.sum(np.sum(full.astype(np.float32) ** 2, axis=1) > 0))
        assert int(merged.n_seen) == n_nonzero
        assert abs(float(merged.frob) - frob) <= 1e-3 * frob + 1e-4
        x = jnp.asarray(np.ones(d, np.float32) / np.sqrt(d))
        ax = float(np.sum((full.astype(np.float64) @ np.asarray(x)) ** 2))
        bx = float(fd_query(merged, x))
        slack = 1e-3 * max(frob, 1.0) + 1e-4
        assert ax - bx >= -slack  # underestimate (shrink only subtracts)
        assert ax - bx <= float(merged.delta_sum) + slack  # certified deficit

    run_property(
        check,
        given=_fd_given,
        cases=_fd_cases(25),
        max_examples=25,
        examples=[_FD_ZERO_ROWS],
    )


# ---------------------------------------------------------------------------
# mg_merge
# ---------------------------------------------------------------------------


_MG_NS, _MG_KS = (40, 90), (6, 12)


def _mg_cases(n_cases):
    rng = np.random.default_rng(11)
    for _ in range(n_cases):
        na, nb = int(rng.choice(_MG_NS)), int(rng.choice(_MG_NS))
        yield {
            "ka": rng.integers(0, 25, na).tolist(),
            "wa": rng.uniform(0.5, 10.0, na).tolist(),
            "kb": rng.integers(0, 25, nb).tolist(),
            "wb": rng.uniform(0.5, 10.0, nb).tolist(),
            "k": int(rng.choice(_MG_KS)),
        }


def _mg_given():
    def stream(key):
        keys = st.shared(
            st.sampled_from(_MG_NS).flatmap(
                lambda n: st.lists(st.integers(0, 24), min_size=n, max_size=n)
            ),
            key=key,
        )
        weights = keys.flatmap(
            lambda ks: st.lists(st.floats(0.5, 10.0), min_size=len(ks), max_size=len(ks))
        )
        return keys, weights

    ka, wa = stream("mg-a")
    kb, wb = stream("mg-b")
    return {"ka": ka, "wa": wa, "kb": kb, "wb": wb, "k": st.sampled_from(_MG_KS)}


def _mg_build(keys, weights, k):
    return mg_update_stream(
        mg_init(k), jnp.asarray(keys, jnp.int32), jnp.asarray(weights, jnp.float32)
    )


def test_mg_merge_commutes_up_to_served_answer():
    """Both merge orders estimate every element identically (weight too)."""

    def check(ka, wa, kb, wb, k):
        sa, sb = _mg_build(ka, wa, k), _mg_build(kb, wb, k)
        ab, ba = mg_merge(sa, sb), mg_merge(sb, sa)
        assert float(ab.weight) == float(ba.weight)
        ia, ib = mg_items(ab), mg_items(ba)
        for e in set(ia) | set(ib):
            assert abs(ia.get(e, 0.0) - ib.get(e, 0.0)) <= 1e-3

    run_property(check, given=_mg_given, cases=_mg_cases(30), max_examples=30)


# Key 6 (weight 2) decrements six unit counters to zero at once; the
# zeroed keys must free their slots, or the merge (which drops them) is
# not an identity.
_MG_ZEROED_COUNTERS = {
    "ka": [0, 1, 2, 3, 4, 5, 6] + [6] * 33,
    "wa": [1.0] * 6 + [2.0] + [1.0] * 33,
    "kb": list(range(25)) + list(range(15)),
    "wb": [1.0] * 40,
    "k": 6,
}


def test_mg_merge_identity_absorption():
    """The empty MG summary is a two-sided identity, bit-identically."""

    def check(ka, wa, kb, wb, k):
        del kb, wb
        sa = _mg_build(ka, wa, k)
        for merged in (mg_merge(sa, mg_init(k)), mg_merge(mg_init(k), sa)):
            assert float(merged.weight) == float(sa.weight)
            assert float(merged.shrink) == float(sa.shrink)
            assert mg_items(merged) == mg_items(sa)

    run_property(
        check,
        given=_mg_given,
        cases=_mg_cases(20),
        max_examples=20,
        examples=[_MG_ZEROED_COUNTERS],
    )


def test_mg_merge_of_splits_keeps_the_mg_guarantee():
    """Merged split streams underestimate, with deficit <= 2W/(k+1)
    (each half contributes a W_i/(k+1) term and the merge adds its own)."""

    def check(ka, wa, kb, wb, k):
        merged = mg_merge(_mg_build(ka, wa, k), _mg_build(kb, wb, k))
        totals: dict[int, float] = {}
        for e, w in zip(list(ka) + list(kb), list(wa) + list(wb)):
            totals[e] = totals.get(e, 0.0) + float(w)
        W = sum(totals.values())
        assert abs(float(merged.weight) - W) <= 1e-3 * W + 1e-3
        items = mg_items(merged)
        for e, true in totals.items():
            est = items.get(e, 0.0)
            assert est <= true + 1e-2
            assert true - est <= 2.0 * W / (k + 1) + 1e-2
        assert -1 not in items  # the EMPTY pad key never surfaces

    run_property(check, given=_mg_given, cases=_mg_cases(30), max_examples=30)


# ---------------------------------------------------------------------------
# quant_merge
# ---------------------------------------------------------------------------


_QU_NS, _QU_EPS = (30, 110), (0.1, 0.2)


def _quant_cases(n_cases):
    rng = np.random.default_rng(13)
    for _ in range(n_cases):
        na, nb = int(rng.choice(_QU_NS)), int(rng.choice(_QU_NS))
        yield {
            "va": rng.normal(scale=100.0, size=na).astype(np.float32).tolist(),
            "vb": rng.normal(scale=100.0, size=nb).astype(np.float32).tolist(),
            "eps": float(rng.choice(_QU_EPS)),
        }


def _quant_given():
    def vals():
        return st.sampled_from(_QU_NS).flatmap(
            lambda n: st.lists(st.floats(-1e4, 1e4, width=32), min_size=n, max_size=n)
        )

    return {"va": vals(), "vb": vals(), "eps": st.sampled_from(_QU_EPS)}


def _quant_build(vals, eps, cap):
    return quant_insert(
        quant_init(cap), np.asarray(vals, np.float32), np.ones(len(vals), np.float32), eps
    )


def test_quant_merge_commutes_up_to_served_answer():
    """Both merge orders serve ranks within their combined certified bands."""

    def check(va, vb, eps):
        cap = int(np.ceil(2.0 / eps)) + 4
        sa, sb = _quant_build(va, eps, cap), _quant_build(vb, eps, cap)
        ab, ba = quant_merge(sa, sb, eps, cap), quant_merge(sb, sa, eps, cap)
        assert float(ab.weight) == float(ba.weight)
        W = float(ab.weight)
        probes = np.percentile(np.asarray(list(va) + list(vb)), [5, 25, 50, 75, 95])
        ra = table_rank(quant_table(ab), probes)
        rb = table_rank(quant_table(ba), probes)
        budget = quant_band(ab) + quant_band(ba) + 1e-3 * W + 1e-4
        assert np.max(np.abs(ra.astype(np.float64) - rb.astype(np.float64))) <= budget

    run_property(check, given=_quant_given, cases=_quant_cases(25), max_examples=25)


def test_quant_merge_identity_absorption():
    """Merging with the all-pad summary preserves weight, band, and ranks."""

    def check(va, vb, eps):
        del vb
        cap = int(np.ceil(2.0 / eps)) + 4
        sa = _quant_build(va, eps, cap)
        W = float(sa.weight)
        probes = np.percentile(np.asarray(va), [10, 50, 90])
        for merged in (
            quant_merge(sa, quant_init(cap), eps, cap),
            quant_merge(quant_init(cap), sa, eps, cap),
        ):
            assert float(merged.weight) == W
            assert quant_band(merged) <= eps * W + 1e-3 * W + 1e-4
            gap = np.abs(
                table_rank(quant_table(merged), probes).astype(np.float64)
                - table_rank(quant_table(sa), probes).astype(np.float64)
            )
            assert np.max(gap) <= quant_band(merged) + quant_band(sa) + 1e-3 * W + 1e-4

    run_property(check, given=_quant_given, cases=_quant_cases(20), max_examples=20)


# A value (0) on both sides: the merged tuple must read each side's
# exact rank at 0, not the other side's next tuple.
_QU_SHARED_VALUE = {
    "va": [0.0] * 9 + [
        6.0, 1.899999976158142, 1950.5760498046875, 3704.3876953125,
        8710.4970703125, 9420.0185546875, 0.3333333432674408, -2.0, -1.5,
        -1409.4228515625, -1410.2528076171875, -3364.281494140625,
        -3674.55029296875, -4905.302734375, -4955.45166015625,
        -7325.44384765625, -8999.662109375, -10000.0, -0.5,
        -2.4966420197412385e-19, -2.6015094779802514e-36,
    ],
    "vb": [0.0] * 21 + [
        -9999.0, -194.7556915283203, -316.2803649902344, -3258.142822265625,
        -6153.875, -7100.24072265625, -2.0, -2.0, -1.0,
    ],
    "eps": 0.1,
}
# A subnormal value, which XLA compares as equal to 0.
_QU_SUBNORMAL = {
    "va": [0.0] * 30,
    "vb": [0.0] * 29 + [6.58610278232664e-44],
    "eps": 0.1,
}


def test_quant_merge_of_splits_keeps_eps_band():
    """Split-then-merge conserves weight exactly and serves every probe
    within its certified band of the exact ranks — the paper's guarantee."""

    def check(va, vb, eps):
        cap = int(np.ceil(2.0 / eps)) + 4
        merged = quant_merge(
            _quant_build(va, eps, cap), _quant_build(vb, eps, cap), eps, cap
        )
        full = np.asarray(list(va) + list(vb), np.float32)
        W = float(full.shape[0])
        assert float(merged.weight) == W
        band = quant_band(merged)
        assert band <= eps * W + 1e-3 * W + 1e-4
        probes = np.unique(np.percentile(full, [5, 25, 50, 75, 95]))
        served = table_rank(quant_table(merged), probes).astype(np.float64)
        exact = exact_ranks(full, np.ones(full.shape[0], np.float32), probes)
        assert np.max(np.abs(served - exact)) <= band + 1e-3 * W + 1e-4

    run_property(
        check,
        given=_quant_given,
        cases=_quant_cases(25),
        max_examples=25,
        examples=[_QU_SHARED_VALUE, _QU_SUBNORMAL],
    )


# ---------------------------------------------------------------------------
# lev_merge
# ---------------------------------------------------------------------------


_LEV_NS, _LEV_D, _LEV_CAPS = (4, 9), 5, (6, 12)


def _lev_cases(n_cases):
    rng = np.random.default_rng(17)
    for _ in range(n_cases):
        na, nb = int(rng.choice(_LEV_NS)), int(rng.choice(_LEV_NS))
        # distinct scores across both sides -> top-cap selection is unique
        scores = rng.permutation(np.arange(1, na + nb + 1)).astype(np.float32)
        yield {
            "ra": rng.normal(size=(na, _LEV_D)).astype(np.float32),
            "sa_": scores[:na],
            "rb": rng.normal(size=(nb, _LEV_D)).astype(np.float32),
            "sb_": scores[na:],
            "cap": int(rng.choice(_LEV_CAPS)),
        }


def _lev_build(rows, scores, cap):
    state, _ = lev_merge_spill(
        lev_init(cap, rows.shape[1]),
        jnp.asarray(rows),
        jnp.asarray(scores),
        jnp.ones(rows.shape[0], jnp.float32),
    )
    return state


def _lev_key(state):
    """Canonical (score, weight, row) triples of the live slots, sorted."""
    scores = np.asarray(state.scores)
    live = scores > 0
    order = np.argsort(-scores[live], kind="stable")
    return (
        scores[live][order],
        np.asarray(state.weights)[live][order],
        np.asarray(state.rows)[live][order],
    )


def test_lev_merge_commutes_and_absorbs_identity():
    """With distinct scores both merge orders keep the same top-cap set;
    the all-pad reservoir is a bit-exact identity."""

    def check(ra, sa_, rb, sb_, cap):
        a, b = _lev_build(ra, sa_, cap), _lev_build(rb, sb_, cap)
        ab, ba = lev_merge(a, b), lev_merge(b, a)
        for ka, kb in zip(_lev_key(ab), _lev_key(ba)):
            np.testing.assert_allclose(ka, kb, rtol=1e-6)
        ident = lev_merge(a, lev_init(cap, ra.shape[1]))
        np.testing.assert_array_equal(np.asarray(ident.rows), np.asarray(a.rows))
        np.testing.assert_array_equal(np.asarray(ident.scores), np.asarray(a.scores))
        np.testing.assert_array_equal(np.asarray(ident.weights), np.asarray(a.weights))

    # a coupled construction (disjoint distinct scores) has no clean
    # strategy encoding — the seeded sweep runs under both install modes
    run_property(check, given=None, cases=_lev_cases(25))


def test_lev_merge_of_splits_matches_stream_and_conserves_mass():
    """Split reservoirs merge to the same top-cap set as the unsplit
    stream, and overflow never loses mass (kept + spilled == total)."""

    def check(ra, sa_, rb, sb_, cap):
        d = ra.shape[1]
        merged = lev_merge(_lev_build(ra, sa_, cap), _lev_build(rb, sb_, cap))
        rows = np.concatenate([ra, rb], axis=0)
        scores = np.concatenate([sa_, sb_])
        direct = _lev_build(rows, scores, cap)
        for km, kd in zip(_lev_key(merged), _lev_key(direct)):
            np.testing.assert_allclose(km, kd, rtol=1e-6)
        # spill accounting: row mass in == row mass kept + row mass spilled
        state, spilled = lev_merge_spill(
            lev_init(cap, d),
            jnp.asarray(rows),
            jnp.asarray(scores),
            jnp.ones(rows.shape[0], jnp.float32),
        )
        total = float(np.sum(rows.astype(np.float64) ** 2))
        kept = float(np.sum(np.asarray(state.rows, np.float64) ** 2))
        lost = float(np.sum(np.asarray(spilled, np.float64) ** 2))
        assert abs(total - (kept + lost)) <= 1e-3 * max(total, 1.0)

    run_property(check, given=None, cases=_lev_cases(25))
