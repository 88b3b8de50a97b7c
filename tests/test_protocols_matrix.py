"""Paper Section 5: matrix tracking protocols — covariance error + messages.

Includes the Appendix-C P4 negative result: its error must NOT be bounded
by eps (that is the paper's claim, reproduced empirically).
"""
import numpy as np
import pytest

from repro.core.protocols import run_matrix_protocol
from repro.data.synthetic import msd_like, pamap_like, site_assignment

N, M, EPS = 30_000, 10, 0.15

# run_matrix_protocol results, memoized for the module on
# (proto, data, m, eps, seed).  P2 draws no randomness, so its seed is
# left out of the key: five tests share one P2 run per stream.
_RUNS: dict = {}


def _run(proto, data, rows, sites, m=M, eps=EPS, seed=0):
    key = (proto, data, m, eps, None if proto == "P2" else seed)
    if key not in _RUNS:
        _RUNS[key] = run_matrix_protocol(proto, rows, sites, m, eps, seed=seed)
    return _RUNS[key]


@pytest.fixture(scope="module")
def lowrank():
    a = pamap_like(N, seed=5)
    sites = site_assignment(N, M, seed=5)
    return a, sites, a.T @ a, float(np.sum(a * a))


@pytest.fixture(scope="module")
def highrank():
    a = msd_like(N, seed=6)
    sites = site_assignment(N, M, seed=6)
    return a, sites, a.T @ a, float(np.sum(a * a))


@pytest.mark.parametrize("proto", ["P1", "P2", "P3"])
@pytest.mark.parametrize("data", ["lowrank", "highrank"])
def test_matrix_error_bound(proto, data, request):
    a, sites, ata, frob = request.getfixturevalue(data)
    res = _run(proto, data, a, sites, seed=1)
    err = res.covariance_error(ata, frob)
    limit = EPS + 1e-3 if proto in ("P1", "P2") else 1.5 * EPS
    assert err <= limit, (proto, data, err)


def test_matrix_p2_cheapest_deterministic(lowrank):
    a, sites, _, _ = lowrank
    m1 = _run("P1", "lowrank", a, sites).comm.total(M)
    m2 = _run("P2", "lowrank", a, sites).comm.total(M)
    assert m2 < m1, "P2 O(m/eps) must beat P1 O(m/eps^2) (paper Table 1)"


def test_matrix_p3wor_beats_p3wr(lowrank):
    """Paper Section 6.2: without-replacement sampling dominates."""
    a, sites, ata, frob = lowrank
    wor = _run("P3", "lowrank", a, sites, seed=2)
    wr = _run("P3wr", "lowrank", a, sites, seed=2)
    assert wor.comm.total(M) < wr.comm.total(M)


def test_matrix_p4_negative_result(lowrank):
    """Appendix C: P4's fixed-basis update cannot bound the error by eps."""
    a, sites, ata, frob = lowrank
    p4 = _run("P4", "lowrank", a, sites, seed=3)
    p2 = _run("P2", "lowrank", a, sites, seed=3)
    err4 = p4.covariance_error(ata, frob)
    err2 = p2.covariance_error(ata, frob)
    assert err4 > err2, "P4 should be clearly worse than P2"
    assert err4 > EPS, f"P4 err {err4} unexpectedly within eps: negative result not reproduced"


def test_matrix_messages_scale_with_m(lowrank):
    a, sites10, _, _ = lowrank
    sites5 = site_assignment(N, 5, seed=9)
    m5 = _run("P2", "lowrank", a, sites5, m=5).comm.total(5)
    m10 = _run("P2", "lowrank", a, sites10).comm.total(10)
    assert m5 < m10, "P2 communication is linear in m (paper Fig 2c/3c)"


def test_matrix_all_beat_naive(lowrank):
    a, sites, _, _ = lowrank
    for proto in ["P2", "P3"]:
        msgs = _run(proto, "lowrank", a, sites).comm.total(M)
        assert msgs < N / 5, (proto, msgs)
