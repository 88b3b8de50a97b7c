"""TPU-native distributed matrix tracking: shard_map super-step protocols.

The paper's transport is an event-driven network (any site may message the
coordinator at any time).  TPU pods speak synchronous SPMD collectives, so
the production engine processes site streams in *super-steps*: every shard
(= site) absorbs a batch of its local rows, evaluates the paper's send
predicates, and a masked ``all_gather``/``psum`` plays the role of the
site->coordinator channel.  The coordinator state is updated redundantly on
every shard (it is a deterministic function of replicated inputs), matching
the paper's remark that the coordinator "may be one of the sites".

Message accounting is at *protocol* level (exactly the messages the
event-driven protocol would send — masked-out lanes count zero), so the
paper's communication bounds remain the yardstick; the cost of the physical
collectives shows up separately in the roofline's collective term.

Super-step skew: delaying a send to the super-step boundary lets a site
overshoot its threshold by at most the batch mass ``batch * beta``; choosing
``batch * beta << (eps/2m) * F_hat`` keeps the end-to-end guarantee intact
(tested in tests/test_distributed.py).

All three matrix protocols are provided with fixed-shape jit-able states:

    * ``P1`` — per-site FD, ship-the-sketch on threshold, FD-merge at C.
    * ``P2`` — the paper's best: per-direction sigma^2 thresholds.  After an
      FD shrink the buffer rows *are* ``sigma_i v_i`` (orthogonal), so the
      send set is a row mask — no extra SVD on the hot path.
    * ``P3`` — distributed priority sampling without replacement (size-s
      classical priority sample kept as a fixed top-(s+1) buffer).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import fd as fdlib
from repro.core import hh as hhlib
from repro.core import leverage as levlib
from repro.core import quantiles as qlib
from repro.core.comm import CommReport, build_report

__all__ = [
    "ProtocolConfig",
    "P1State",
    "P2State",
    "P3State",
    "HHP1State",
    "QuantP1State",
    "LevP1State",
    "p1_init",
    "p1_step",
    "p2_init",
    "p2_step",
    "p3_init",
    "p3_step",
    "hh_p1_init",
    "hh_p1_step",
    "hh_estimates",
    "hh_w_hat",
    "quant_p1_init",
    "quant_p1_step",
    "quant_p1_table",
    "quant_p1_w_hat",
    "lev_p1_init",
    "lev_p1_step",
    "lev_p1_table",
    "lev_p1_mass",
    "lev_p1_lambda",
    "p2_query",
    "p3_matrix",
    "protocol_matrix",
    "protocol_frob",
    "make_protocol_runner",
    "make_packed_runner",
    "unstack_packed",
    "PackedRunner",
    "PACKABLE_PROTOCOLS",
]


class ProtocolConfig(NamedTuple):
    """Static shard-protocol configuration (size defaults via ``resolved``)."""
    eps: float
    m: int  # number of sites == mesh axis size
    d: int  # row dimensionality
    axis: str = "sites"
    l_site: int = 0  # site sketch rows (0 -> ceil(4/eps), paper default)
    l_coord: int = 0  # coordinator sketch rows (0 -> ceil(4/eps))
    s: int = 0  # P3 sample size (0 -> ceil(1/eps^2 * log(1/eps)))
    k: int = 0  # HH MG counters (0 -> ceil(2/eps), the MG_{eps/2} default)
    q_cap: int = 0  # quantile summary capacity (0 -> ceil(8/eps) + 8)
    lev_cap: int = 0  # leverage reservoir capacity (0 -> ceil(4/eps), floor 16)
    use_pallas: bool = False

    def resolved(self) -> "ProtocolConfig":
        """Fill size defaults: sketch rows, sample size, MG counters,
        quantile cap, leverage reservoir cap."""
        import math

        l_default = max(2, math.ceil(4.0 / self.eps))
        s_default = max(8, math.ceil((1.0 / self.eps**2) * math.log(max(math.e, 1.0 / self.eps))))
        return self._replace(
            l_site=self.l_site or l_default,
            l_coord=self.l_coord or l_default,
            s=self.s or s_default,
            k=self.k or max(2, math.ceil(2.0 / self.eps)),
            q_cap=self.q_cap or max(32, math.ceil(8.0 / self.eps) + 8),
            lev_cap=self.lev_cap or levlib.default_cap(self.eps),
        )


class CommCounters(NamedTuple):
    """Jit-able protocol-level message counters (the shard engine's CommLog)."""
    scalar_msgs: jax.Array  # i32 — protocol-level scalar messages
    row_msgs: jax.Array  # i32 — protocol-level row messages
    broadcast_events: jax.Array  # i32

    @staticmethod
    def zero() -> "CommCounters":
        """All-zero counters."""
        z = jnp.zeros((), jnp.int32)
        return CommCounters(z, z, z)

    def report(self, m: int) -> CommReport:
        """Collapse the jit-able counters to the engine-agnostic report."""
        return build_report(
            scalar_msgs=self.scalar_msgs,
            row_msgs=self.row_msgs,
            broadcast_events=self.broadcast_events,
            m=m,
        )


def _row_sq(x: jax.Array) -> jax.Array:
    return jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)


# ---------------------------------------------------------------------------
# Protocol 1 — batched FD merge
# ---------------------------------------------------------------------------


class P1State(NamedTuple):
    """Matrix P1 shard state: per-site FD + replicated coordinator FD/totals."""
    site_fd: fdlib.FDState  # per-shard
    f_i: jax.Array  # per-shard () f32 — mass since last ship
    coord_fd: fdlib.FDState  # replicated
    f_c: jax.Array  # replicated — mass received at C
    f_hat: jax.Array  # replicated — broadcast estimate
    comm: CommCounters


def p1_init(cfg: ProtocolConfig) -> P1State:
    """Initial P1 state for one site (tiled per shard by the runner)."""
    cfg = cfg.resolved()
    return P1State(
        site_fd=fdlib.fd_init(cfg.l_site, cfg.d),
        f_i=jnp.zeros((), jnp.float32),
        coord_fd=fdlib.fd_init(cfg.l_coord, cfg.d),
        f_c=jnp.zeros((), jnp.float32),
        f_hat=jnp.ones((), jnp.float32),
        comm=CommCounters.zero(),
    )


def p1_step(cfg: ProtocolConfig, st: P1State, rows: jax.Array) -> P1State:
    """One super-step; call inside shard_map with ``rows`` = local (b, d)."""
    cfg = cfg.resolved()
    site_fd = fdlib.fd_update_stream(st.site_fd, rows, use_pallas=cfg.use_pallas)
    f_i = st.f_i + jnp.sum(_row_sq(rows))

    send = f_i >= (cfg.eps / (2 * cfg.m)) * st.f_hat
    payload = jnp.where(send, fdlib.fd_matrix(site_fd), 0.0)  # (l_site, d)
    gathered = lax.all_gather(payload, cfg.axis)  # (m, l_site, d)
    coord_fd = fdlib.fd_update_stream(
        st.coord_fd, gathered.reshape(-1, cfg.d), use_pallas=cfg.use_pallas
    )
    shipped_rows = lax.psum(
        jnp.where(send, jnp.sum(_row_sq(fdlib.fd_matrix(site_fd)) > 0), 0), cfg.axis
    )
    n_scalar = lax.psum(send.astype(jnp.int32), cfg.axis)

    f_c = st.f_c + lax.psum(jnp.where(send, f_i, 0.0), cfg.axis)
    f_i = jnp.where(send, 0.0, f_i)
    # Reset shipped sketches.
    empty = fdlib.fd_init(cfg.l_site, cfg.d)
    site_fd = jax.tree.map(lambda a, b: jnp.where(send, b, a), site_fd, empty)

    rebroadcast = f_c / st.f_hat > 1.0 + cfg.eps / 2.0
    f_hat = jnp.where(rebroadcast, f_c, st.f_hat)
    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs + n_scalar,
        row_msgs=st.comm.row_msgs + shipped_rows.astype(jnp.int32),
        broadcast_events=st.comm.broadcast_events + rebroadcast.astype(jnp.int32),
    )
    return P1State(site_fd, f_i, coord_fd, f_c, f_hat, comm)


# ---------------------------------------------------------------------------
# Protocol 2 — per-direction thresholds (the paper's best)
# ---------------------------------------------------------------------------


class P2State(NamedTuple):
    """Matrix P2 shard state: per-site FD + replicated coordinator FD/thresholds."""
    site_fd: fdlib.FDState  # per-shard; buffer rows are sigma_i v_i
    f_j: jax.Array  # per-shard () f32 — scalar-message accumulator
    coord_fd: fdlib.FDState  # replicated
    f_hat: jax.Array  # replicated
    n_msg: jax.Array  # replicated i32 — scalar msgs since last broadcast
    comm: CommCounters


def p2_init(cfg: ProtocolConfig) -> P2State:
    """Initial P2 state for one site (tiled per shard by the runner)."""
    cfg = cfg.resolved()
    return P2State(
        site_fd=fdlib.fd_init(cfg.l_site, cfg.d),
        f_j=jnp.zeros((), jnp.float32),
        coord_fd=fdlib.fd_init(cfg.l_coord, cfg.d),
        f_hat=jnp.ones((), jnp.float32),
        n_msg=jnp.zeros((), jnp.int32),
        comm=CommCounters.zero(),
    )


def p2_step(cfg: ProtocolConfig, st: P2State, rows: jax.Array) -> P2State:
    """One P2 super-step; call inside shard_map with ``rows`` = local (b, d)."""
    cfg = cfg.resolved()
    # -- scalar totals (Algorithm 5.3 first half) --
    f_j = st.f_j + jnp.sum(_row_sq(rows))
    send_scalar = f_j >= (cfg.eps / cfg.m) * st.f_hat
    f_hat = st.f_hat + lax.psum(jnp.where(send_scalar, f_j, 0.0), cfg.axis)
    n_sent = lax.psum(send_scalar.astype(jnp.int32), cfg.axis)
    f_j = jnp.where(send_scalar, 0.0, f_j)
    n_msg = st.n_msg + n_sent
    rebroadcast = n_msg >= cfg.m
    n_msg = jnp.where(rebroadcast, 0, n_msg)

    # -- direction sends (Algorithm 5.3 second half) --
    # After fd_update the buffer rows are orthogonal sigma_i v_i: the svd in
    # Algorithm 5.3 is already materialised; the send set is a row mask.
    # Only the first l_site buffer rows can be non-zero post-shrink (the
    # shrink weights vanish past l), so the gather ships (l_site, d) per
    # site and the coordinator absorbs m*l_site rows — half the chunked
    # shrinks of gathering the raw 2l buffer, with no phantom all-zero
    # chunks spending shrink mass at the coordinator.
    site_fd = fdlib.fd_update_stream(st.site_fd, rows, use_pallas=cfg.use_pallas)
    buf = site_fd.buf
    live = buf[: cfg.l_site]
    sq = _row_sq(live)
    mask = sq >= (cfg.eps / cfg.m) * f_hat
    payload = jnp.where(mask[:, None], live, 0.0)
    site_fd = site_fd._replace(
        buf=buf.at[: cfg.l_site].set(jnp.where(mask[:, None], 0.0, live))
    )
    gathered = lax.all_gather(payload, cfg.axis)  # (m, l_site, d)
    coord_fd = fdlib.fd_update_stream(
        st.coord_fd, gathered.reshape(-1, cfg.d), use_pallas=cfg.use_pallas
    )
    n_rows = lax.psum(jnp.sum(mask.astype(jnp.int32)), cfg.axis)

    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs + n_sent,
        row_msgs=st.comm.row_msgs + n_rows,
        broadcast_events=st.comm.broadcast_events + rebroadcast.astype(jnp.int32),
    )
    return P2State(site_fd, f_j, coord_fd, f_hat, n_msg, comm)


def p2_query(st: P2State, x: jax.Array) -> jax.Array:
    """Coordinator estimate of ||A x||^2 (callable outside shard_map)."""
    return fdlib.fd_query(st.coord_fd, x)


# ---------------------------------------------------------------------------
# Protocol 3 — distributed priority sampling (without replacement)
# ---------------------------------------------------------------------------


class P3State(NamedTuple):
    """Matrix P3 shard state: per-site PRNG + replicated priority-sample buffer."""
    rng: jax.Array  # per-shard PRNG key
    tau: jax.Array  # replicated () f32 — round threshold
    buf_rows: jax.Array  # replicated (s+1, d) — top-priority rows
    buf_w: jax.Array  # replicated (s+1,)
    buf_rho: jax.Array  # replicated (s+1,)
    comm: CommCounters


def p3_init(cfg: ProtocolConfig, seed: int = 0) -> P3State:
    """Initial P3 state (per-site PRNG keys are installed by the runner)."""
    cfg = cfg.resolved()
    return P3State(
        rng=jax.random.key(seed),
        tau=jnp.ones((), jnp.float32),
        buf_rows=jnp.zeros((cfg.s + 1, cfg.d), jnp.float32),
        buf_w=jnp.zeros((cfg.s + 1,), jnp.float32),
        buf_rho=jnp.zeros((cfg.s + 1,), jnp.float32),
        comm=CommCounters.zero(),
    )


def p3_step(cfg: ProtocolConfig, st: P3State, rows: jax.Array) -> P3State:
    """One P3 super-step; call inside shard_map with ``rows`` = local (b, d)."""
    cfg = cfg.resolved()
    site = lax.axis_index(cfg.axis)
    key = jax.random.fold_in(st.rng, site)
    key, sub = jax.random.split(key)
    # Keep per-shard streams decorrelated across steps: carry the split key.
    new_rng = jax.random.split(st.rng)[0]

    w = _row_sq(rows)
    u = jax.random.uniform(sub, w.shape, minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    rho = w / u
    mask = rho >= st.tau
    n_sent = lax.psum(jnp.sum(mask.astype(jnp.int32)), cfg.axis)

    cand_rows = jnp.where(mask[:, None], rows.astype(jnp.float32), 0.0)
    cand_w = jnp.where(mask, w, 0.0)
    cand_rho = jnp.where(mask, rho, 0.0)
    g_rows = lax.all_gather(cand_rows, cfg.axis).reshape(-1, cfg.d)
    g_w = lax.all_gather(cand_w, cfg.axis).reshape(-1)
    g_rho = lax.all_gather(cand_rho, cfg.axis).reshape(-1)

    all_rho = jnp.concatenate([st.buf_rho, g_rho])
    all_w = jnp.concatenate([st.buf_w, g_w])
    all_rows = jnp.concatenate([st.buf_rows, g_rows])
    top_rho, top_idx = lax.top_k(all_rho, cfg.s + 1)
    buf_rows = all_rows[top_idx]
    buf_w = all_w[top_idx]
    buf_rho = top_rho

    # Round advance: double tau while >= s buffered items exceed 2*tau.
    def cond(tau):
        return jnp.sum(buf_rho >= 2.0 * tau) >= cfg.s

    def body(tau):
        return tau * 2.0

    new_tau = lax.while_loop(cond, body, st.tau)
    n_broadcast = jnp.round(jnp.log2(new_tau / st.tau)).astype(jnp.int32)

    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs,
        row_msgs=st.comm.row_msgs + n_sent,
        broadcast_events=st.comm.broadcast_events + n_broadcast,
    )
    return P3State(new_rng, new_tau, buf_rows, buf_w, buf_rho, comm)


def p3_matrix(st: P3State) -> jax.Array:
    """Coordinator estimate matrix B from the priority sample (s rows).

    Classical priority-sample estimator: tau_hat = smallest buffered
    priority; every kept row is rescaled to squared norm max(w, tau_hat).
    """
    tau_hat = jnp.min(jnp.where(st.buf_rho > 0, st.buf_rho, jnp.inf))
    tau_hat = jnp.where(jnp.isfinite(tau_hat), tau_hat, 0.0)
    smallest = jnp.argmin(jnp.where(st.buf_rho > 0, st.buf_rho, jnp.inf))
    keep = (st.buf_rho > 0) & (jnp.arange(st.buf_rho.shape[0]) != smallest)
    wbar = jnp.maximum(st.buf_w, tau_hat)
    scale = jnp.sqrt(wbar / jnp.maximum(st.buf_w, 1e-30))
    return jnp.where(keep[:, None], st.buf_rows * scale[:, None], 0.0)


# ---------------------------------------------------------------------------
# Weighted heavy hitters, protocol 1 — batched Misra--Gries merge.
#
# The HH twin of matrix P1: every shard (= site) runs a weighted MG_{eps/2}
# summary over its local (element, weight) stream; when a site's weight
# since its last ship crosses ``(eps/2m) * w_hat`` it ships the whole
# summary, and the coordinator folds shipped summaries in with ``mg_merge``
# (the mergeable-summaries merge, so the coordinator error stays one
# ``W/(k+1)`` term per merge depth).  Message units follow the paper: a
# shipped summary of ``r`` live counters costs ``r`` item messages plus one
# scalar, and a ``w_hat`` rebroadcast costs ``m``.
# ---------------------------------------------------------------------------


class HHP1State(NamedTuple):
    """HH P1 shard state: per-site MG summary + replicated coordinator MG/totals."""
    site_mg: hhlib.MGState  # per-shard
    w_i: jax.Array  # per-shard () f32 — weight since last ship
    coord_mg: hhlib.MGState  # replicated
    w_c: jax.Array  # replicated — weight received at C
    w_hat: jax.Array  # replicated — broadcast estimate
    comm: CommCounters


def hh_p1_init(cfg: ProtocolConfig) -> HHP1State:
    """Initial HH P1 state for one site (tiled per shard by the runner)."""
    cfg = cfg.resolved()
    return HHP1State(
        site_mg=hhlib.mg_init(cfg.k),
        w_i=jnp.zeros((), jnp.float32),
        coord_mg=hhlib.mg_init(cfg.k),
        w_c=jnp.zeros((), jnp.float32),
        w_hat=jnp.ones((), jnp.float32),
        comm=CommCounters.zero(),
    )


def hh_p1_step(cfg: ProtocolConfig, st: HHP1State, pairs) -> HHP1State:
    """One super-step; ``pairs`` = local ``(keys i32 (b,), weights f32 (b,))``."""
    cfg = cfg.resolved()
    keys, weights = pairs
    site_mg = hhlib.mg_update_stream(st.site_mg, keys, weights)
    w_i = st.w_i + jnp.sum(weights.astype(jnp.float32))

    send = w_i >= (cfg.eps / (2 * cfg.m)) * st.w_hat
    # Masked ship: a non-sender contributes the empty summary, which is the
    # identity element of mg_merge, so the gather-then-fold below is exactly
    # "the coordinator merges what was shipped".
    pay = hhlib.MGState(
        keys=jnp.where(send, site_mg.keys, hhlib.EMPTY),
        counts=jnp.where(send, site_mg.counts, 0.0),
        weight=jnp.where(send, site_mg.weight, 0.0),
        shrink=jnp.where(send, site_mg.shrink, 0.0),
    )
    gathered = jax.tree.map(lambda a: lax.all_gather(a, cfg.axis), pay)  # (m, ...)
    coord = st.coord_mg
    for j in range(cfg.m):  # static unroll: m is the mesh axis size
        coord = hhlib.mg_merge(coord, jax.tree.map(lambda a: a[j], gathered))

    live = jnp.sum((site_mg.keys != hhlib.EMPTY).astype(jnp.int32))
    shipped = lax.psum(jnp.where(send, live, 0), cfg.axis)
    n_scalar = lax.psum(send.astype(jnp.int32), cfg.axis)

    w_c = st.w_c + lax.psum(jnp.where(send, w_i, 0.0), cfg.axis)
    w_i = jnp.where(send, 0.0, w_i)
    # Reset shipped site summaries.
    empty = hhlib.mg_init(cfg.k)
    site_mg = jax.tree.map(lambda a, b: jnp.where(send, b, a), site_mg, empty)

    rebroadcast = w_c / st.w_hat > 1.0 + cfg.eps / 2.0
    w_hat = jnp.where(rebroadcast, w_c, st.w_hat)
    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs + n_scalar,
        row_msgs=st.comm.row_msgs + shipped.astype(jnp.int32),
        broadcast_events=st.comm.broadcast_events + rebroadcast.astype(jnp.int32),
    )
    return HHP1State(site_mg, w_i, coord, w_c, w_hat, comm)


def hh_estimates(st: HHP1State) -> dict[int, float]:
    """The coordinator's current ``{element: weight-estimate}`` map."""
    return hhlib.mg_items(st.coord_mg)


def hh_w_hat(st: HHP1State) -> float:
    """Coordinator estimate of the total stream weight ``W`` (HH frob analog)."""
    return float(st.w_hat)


# ---------------------------------------------------------------------------
# Distributed quantiles, protocol 1 — batched summary merge.
#
# The quantile twin of hh_p1_step: every shard (= site) maintains a fixed-
# shape GK-style ``QuantState`` over its local (value, weight) stream;
# when its cumulative weight has grown by a ``1 + eps/4`` factor since the
# last ship (with an ``eps/(4m) * w_hat`` floor so early items batch up) it
# ships the whole summary, and the coordinator folds shipped summaries in
# with ``quant_merge`` — the all-pad summary is the merge identity, so a
# non-sender's masked payload is exactly "nothing was shipped".  Message
# units follow the paper: a shipped summary of ``r`` live tuples costs
# ``r`` item messages plus one scalar, a ``w_hat`` rebroadcast costs m.
# ---------------------------------------------------------------------------


class QuantP1State(NamedTuple):
    """Quantile P1 shard state: per-site summary + replicated coordinator summary."""
    site_q: qlib.QuantState  # per-shard
    w_i: jax.Array  # per-shard () f32 — cumulative site weight
    w_pushed: jax.Array  # per-shard () f32 — cumulative weight at last ship
    coord_q: qlib.QuantState  # replicated
    w_hat: jax.Array  # replicated — broadcast estimate
    comm: CommCounters


def quant_p1_init(cfg: ProtocolConfig) -> QuantP1State:
    """Initial quantile P1 state for one site (tiled per shard by the runner)."""
    cfg = cfg.resolved()
    return QuantP1State(
        site_q=qlib.quant_init(cfg.q_cap),
        w_i=jnp.zeros((), jnp.float32),
        w_pushed=jnp.zeros((), jnp.float32),
        coord_q=qlib.quant_init(cfg.q_cap),
        w_hat=jnp.ones((), jnp.float32),
        comm=CommCounters.zero(),
    )


def quant_p1_step(cfg: ProtocolConfig, st: QuantP1State, pairs) -> QuantP1State:
    """One super-step; ``pairs`` = local ``(values f32 (b,), weights f32 (b,))``."""
    cfg = cfg.resolved()
    values, weights = pairs
    site_q = qlib.quant_insert(st.site_q, values, weights, cfg.eps / 4.0)
    w_i = st.w_i + jnp.sum(weights.astype(jnp.float32))
    unpushed = w_i - st.w_pushed

    send = (w_i >= (1.0 + cfg.eps / 4.0) * st.w_pushed) & (
        unpushed >= (cfg.eps / (4.0 * cfg.m)) * st.w_hat
    )
    # Masked ship: a non-sender contributes the all-pad summary, which is
    # the identity of quant_merge, so the gather-then-fold below is exactly
    # "the coordinator merges what was shipped".
    pay = qlib.QuantState(
        values=jnp.where(send, site_q.values, jnp.inf),
        g=jnp.where(send, site_q.g, 0.0),
        delta=jnp.where(send, site_q.delta, 0.0),
        wv=jnp.where(send, site_q.wv, 0.0),
        weight=jnp.where(send, site_q.weight, 0.0),
    )
    gathered = jax.tree.map(lambda a: lax.all_gather(a, cfg.axis), pay)  # (m, ...)
    coord = st.coord_q
    for j in range(cfg.m):  # static unroll: m is the mesh axis size
        coord = qlib.quant_merge(
            coord, jax.tree.map(lambda a: a[j], gathered), cfg.eps / 2.0, cfg.q_cap
        )

    live = jnp.sum(jnp.isfinite(site_q.values).astype(jnp.int32))
    shipped = lax.psum(jnp.where(send, live, 0), cfg.axis)
    n_scalar = lax.psum(send.astype(jnp.int32), cfg.axis)

    w_pushed = jnp.where(send, w_i, st.w_pushed)
    # Reset shipped site summaries.
    empty = qlib.quant_init(cfg.q_cap)
    site_q = jax.tree.map(lambda a, b: jnp.where(send, b, a), site_q, empty)

    rebroadcast = coord.weight / st.w_hat > 1.0 + cfg.eps / 2.0
    w_hat = jnp.where(rebroadcast, coord.weight, st.w_hat)
    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs + n_scalar,
        row_msgs=st.comm.row_msgs + shipped.astype(jnp.int32),
        broadcast_events=st.comm.broadcast_events + rebroadcast.astype(jnp.int32),
    )
    return QuantP1State(site_q, w_i, w_pushed, coord, w_hat, comm)


def quant_p1_table(st: QuantP1State) -> "jax.Array":
    """The coordinator's published ``(n, 2)`` [value, rank-estimate] table."""
    return qlib.quant_table(st.coord_q)


def quant_p1_w_hat(st: QuantP1State) -> float:
    """Coordinator estimate of the total stream weight (quantile frob analog)."""
    return float(st.coord_q.weight)


# ---------------------------------------------------------------------------
# Leverage-score row sampling, protocol 1 — deterministic threshold
# forwarding over masked collectives.
#
# The leverage twin of quant_p1_step, mirroring the event-driven
# ``LeverageP1Stream``: every shard (= site) scores its local rows against
# the replicated coordinator factor ``(B^T B + lambda I)^+`` (B = residual
# FD rows + the kept reservoir, lambda = eps * F_hat).  Rows whose score
# crosses the broadcast threshold ``theta`` are shipped outright through a
# masked ``all_gather`` and folded into the replicated reservoir with
# ``lev_merge_spill`` (the all-pad candidate batch is the merge identity);
# reservoir spill folds into the residual FD sketch, so overflow never
# loses mass.  Everything below threshold rides the site FD sketch,
# shipped on the matrix-P1 mass threshold ``(eps/2m) F_hat``.  Message
# units follow the paper: a forwarded row or shipped sketch row costs one
# row message, a sketch ship one scalar, and a rebroadcast (F_hat growth
# or theta doubling) costs m.  The scoring factor refreshes ONLY on those
# counted broadcasts, so sites never consume coordinator state that was
# not paid for (the same information boundary f_hat/w_hat observe).
# ---------------------------------------------------------------------------


class LevP1State(NamedTuple):
    """Leverage P1 shard state: per-site FD + replicated reservoir/factor data."""
    site_fd: fdlib.FDState  # per-shard — residual (below-threshold) rows only
    f_i: jax.Array  # per-shard () f32 — residual mass since last ship
    coord_fd: fdlib.FDState  # replicated — residual sketch at C
    res: levlib.LevState  # replicated — kept (row, score, weight) reservoir
    f_res: jax.Array  # replicated — residual mass received at C
    f_hat: jax.Array  # replicated — broadcast estimate of ||A||_F^2
    theta: jax.Array  # replicated — forwarding threshold
    factor: jax.Array  # replicated (d, d) — last BROADCAST scoring factor
    comm: CommCounters


def lev_p1_init(cfg: ProtocolConfig) -> LevP1State:
    """Initial leverage P1 state for one site (tiled per shard by the runner)."""
    cfg = cfg.resolved()
    lam0 = levlib.default_lambda(cfg.eps, 1.0)
    return LevP1State(
        site_fd=fdlib.fd_init(cfg.l_site, cfg.d),
        f_i=jnp.zeros((), jnp.float32),
        coord_fd=fdlib.fd_init(cfg.l_coord, cfg.d),
        res=levlib.lev_init(cfg.lev_cap, cfg.d),
        f_res=jnp.zeros((), jnp.float32),
        f_hat=jnp.ones((), jnp.float32),
        theta=jnp.ones((), jnp.float32),
        factor=jnp.eye(cfg.d, dtype=jnp.float32) / jnp.float32(lam0),
        comm=CommCounters.zero(),
    )


def _lev_factor(coord_fd: fdlib.FDState, res: levlib.LevState,
                f_hat: jax.Array, cfg: ProtocolConfig) -> jax.Array:
    """The scoring factor ``(B^T B + lambda I)^{-1}`` (d, d) at broadcast time.

    B stacks the residual FD rows and the kept reservoir rows; the ridge
    ``lambda = eps * max(f_hat, 1)`` keeps the Gram positive definite, so
    a plain eigh-based inverse is exact and jit-stable.
    """
    ball = jnp.concatenate([fdlib.fd_matrix(coord_fd), res.rows])
    lam = jnp.float32(cfg.eps) * jnp.maximum(f_hat, 1.0)
    g = jnp.matmul(ball.T, ball, preferred_element_type=jnp.float32)
    g = g + lam * jnp.eye(cfg.d, dtype=jnp.float32)
    evals, evecs = jnp.linalg.eigh(g)
    inv = (evecs / jnp.maximum(evals, 1e-30)[None, :]) @ evecs.T
    return inv


def lev_p1_step(cfg: ProtocolConfig, st: LevP1State, rows: jax.Array) -> LevP1State:
    """One super-step; call inside shard_map with ``rows`` = local (b, d)."""
    cfg = cfg.resolved()
    if rows.shape[0] == 0:  # static shape: nothing to absorb
        return st
    rows = rows.astype(jnp.float32)
    # Score against the LAST BROADCAST factor: between counted broadcasts
    # the sites' view of the coordinator summary is frozen, exactly like
    # the event engine's self._factor.
    scores = jnp.sum((rows @ st.factor) * rows, axis=1)
    # A site forwards at most lev_cap rows per super-step (the reservoir
    # can absorb no more): the top local scorers above theta.  Everything
    # else rides the FD residual, so the envelope is indifferent to the
    # cap — it only bounds per-step communication.
    k_local = min(cfg.lev_cap, scores.shape[0])
    kth = lax.top_k(scores, k_local)[0][-1]
    fwd = (scores >= st.theta) & (scores >= kth)
    n_fwd = lax.psum(jnp.sum(fwd.astype(jnp.int32)), cfg.axis)

    # Masked ship of forwarded candidates: a non-forwarded lane contributes
    # a zero-score triple, the identity of lev_merge, so gather-then-merge
    # is exactly "the coordinator keeps what was forwarded".
    cand_rows = lax.all_gather(
        jnp.where(fwd[:, None], rows, 0.0), cfg.axis
    ).reshape(-1, cfg.d)
    cand_scores = lax.all_gather(jnp.where(fwd, scores, 0.0), cfg.axis).reshape(-1)
    res, spilled = levlib.lev_merge_spill(
        st.res, cand_rows, cand_scores, jnp.ones_like(cand_scores)
    )
    # Reservoir spill folds into the residual sketch (coordinator-local):
    # overflow raises theta, it never drops mass.
    coord_fd = fdlib.fd_update_stream(st.coord_fd, spilled, use_pallas=cfg.use_pallas)
    spill_mass = jnp.sum(_row_sq(spilled))
    overflow = spill_mass > 0.0
    # Threshold propagation: once the reservoir overflows, the broadcast
    # entry bar jumps to the smallest kept score (doubling at minimum) —
    # a site learns it must beat the incumbents to forward at all.
    theta = jnp.where(
        overflow, jnp.maximum(st.theta * 2.0, res.scores[-1]), st.theta
    )

    # Below-threshold rows ride the site FD sketch (zero rows are free).
    site_rows = jnp.where(fwd[:, None], 0.0, rows)
    site_fd = fdlib.fd_update_stream(st.site_fd, site_rows, use_pallas=cfg.use_pallas)
    f_i = st.f_i + jnp.sum(_row_sq(site_rows))

    send = f_i >= (cfg.eps / (2 * cfg.m)) * st.f_hat
    payload = jnp.where(send, fdlib.fd_matrix(site_fd), 0.0)  # (l_site, d)
    gathered = lax.all_gather(payload, cfg.axis)  # (m, l_site, d)
    coord_fd = fdlib.fd_update_stream(
        coord_fd, gathered.reshape(-1, cfg.d), use_pallas=cfg.use_pallas
    )
    shipped_rows = lax.psum(
        jnp.where(send, jnp.sum(_row_sq(fdlib.fd_matrix(site_fd)) > 0), 0), cfg.axis
    )
    n_scalar = lax.psum(send.astype(jnp.int32), cfg.axis)

    f_res = st.f_res + spill_mass + lax.psum(jnp.where(send, f_i, 0.0), cfg.axis)
    f_i = jnp.where(send, 0.0, f_i)
    empty = fdlib.fd_init(cfg.l_site, cfg.d)
    site_fd = jax.tree.map(lambda a, b: jnp.where(send, b, a), site_fd, empty)

    mass_kept = jnp.sum(_row_sq(res.rows))
    rebroadcast = (f_res + mass_kept) / st.f_hat > 1.0 + cfg.eps / 2.0
    f_hat = jnp.where(rebroadcast, f_res + mass_kept, st.f_hat)
    # The factor refreshes only when a broadcast is actually counted
    # (mass growth or theta doubling) — sites keep scoring against the
    # stale one until then.
    did_broadcast = rebroadcast | overflow
    factor = jnp.where(did_broadcast, _lev_factor(coord_fd, res, f_hat, cfg),
                       st.factor)
    comm = CommCounters(
        scalar_msgs=st.comm.scalar_msgs + n_scalar,
        row_msgs=st.comm.row_msgs + shipped_rows.astype(jnp.int32) + n_fwd,
        broadcast_events=st.comm.broadcast_events
        + rebroadcast.astype(jnp.int32)
        + overflow.astype(jnp.int32),
    )
    return LevP1State(site_fd, f_i, coord_fd, res, f_res, f_hat, theta, factor,
                      comm)


def lev_p1_table(cfg: ProtocolConfig, st: LevP1State) -> "np.ndarray":
    """The coordinator's published ``(n, d+2)`` [row | score | weight] table.

    Assembled by the shared ``core.leverage.build_p1_table`` encoder (kept
    reservoir rows at weight 1 beside the live residual-sketch rows at
    weight 1) — the same deterministic estimator the event stream
    publishes, so ``table_subspace`` inherits the FD envelope on both
    engines.
    """
    import numpy as np

    cfg = cfg.resolved()
    scores = np.asarray(st.res.scores, np.float64)
    live = scores > 0
    return levlib.build_p1_table(
        np.asarray(st.res.rows, np.float64)[live],
        scores[live],
        np.asarray(fdlib.fd_matrix(st.coord_fd)),
        lev_p1_lambda(cfg, st),
    )


def lev_p1_mass(st: LevP1State) -> float:
    """Coordinator estimate of ``||A||_F^2`` (residual + kept reservoir mass)."""
    return float(st.f_res) + float(jnp.sum(_row_sq(st.res.rows)))


def lev_p1_lambda(cfg: ProtocolConfig, st: LevP1State) -> float:
    """The live ridge ``lambda = eps * max(f_hat, 1)`` of a shard state.

    Based on the *broadcast* mass estimate — the same basis the in-step
    scoring factor uses — so the score column of a published table and a
    served score query for the same vector agree beyond timing lag.
    """
    return levlib.default_lambda(cfg.eps, float(st.f_hat))


# ---------------------------------------------------------------------------
# Runner: wraps a protocol step in shard_map over a mesh axis.
# ---------------------------------------------------------------------------

_INITS = {"P1": p1_init, "P2": p2_init, "P3": p3_init, "HHP1": hh_p1_init,
          "QP1": quant_p1_init, "LP1": lev_p1_init}
_STEPS = {"P1": p1_step, "P2": p2_step, "P3": p3_step, "HHP1": hh_p1_step,
          "QP1": quant_p1_step, "LP1": lev_p1_step}
_MATRICES = {
    "P1": lambda st: fdlib.fd_matrix(st.coord_fd),
    "P2": lambda st: fdlib.fd_matrix(st.coord_fd),
    "P3": p3_matrix,
}


def protocol_matrix(protocol: str, state) -> jax.Array:
    """The coordinator's sketch matrix B for any protocol state (uniform)."""
    return _MATRICES[protocol](state)


def protocol_frob(protocol: str, state, matrix=None) -> float:
    """Coordinator estimate of the stream mass ``||A||_F^2`` (uniform).

    P1/P2 carry the coordinator's running broadcast estimate ``f_hat``
    (within (1+eps) of ``||A||_F^2``); P3's priority-sample estimator matrix
    preserves the stream mass by construction, so its own Frobenius norm
    stands in (pass ``matrix`` to reuse an already-materialized sketch).
    """
    if protocol in ("P1", "P2"):
        return float(state.f_hat)
    b = protocol_matrix(protocol, state) if matrix is None else matrix
    return float(jnp.sum(b * b))


# Per-site state leaves (leading m axis sharded over cfg.axis) per protocol;
# every other leaf is replicated.  Shared by both runner factories.
_PER_SITE_LEAVES = {
    "P1": ("site_fd", "f_i"),
    "P2": ("site_fd", "f_j"),
    "P3": ("rng",),
    "HHP1": ("site_mg", "w_i"),
    "QP1": ("site_q", "w_i", "w_pushed"),
    "LP1": ("site_fd", "f_i"),
}

# Protocols safe to advance as a stacked multi-tenant pack: their step is a
# deterministic function of (state, rows) for which appended zero rows are
# exact no-ops on every served quantity (zero-norm rows add nothing to site
# sketches, masses, thresholds, or candidate sets).  P3 is excluded — its
# per-step PRNG draw shape follows the padded row count, so padding would
# change the sample — as are the pair-input protocols (HHP1/QP1), whose
# weighted items cannot be zero-padded without perturbing the summaries.
PACKABLE_PROTOCOLS = ("P1", "P2", "LP1")

# Jitted (state0, step) runners keyed on (protocol, cfg, mesh): the T-th
# same-shape tenant reuses the first tenant's trace instead of re-tracing.
_RUNNER_CACHE: dict = {}
_PACKED_RUNNER_CACHE: dict = {}


def make_protocol_runner(protocol: str, cfg: ProtocolConfig, mesh: jax.sharding.Mesh):
    """Return ``(init_state, step)``: one jitted shard_map super-step.

    For the matrix protocols and ``LP1`` (leverage sampling)
    ``step(state, rows)`` consumes a global ``(m * b, d)`` array sharded
    over ``cfg.axis``; for ``HHP1`` (element keys) and ``QP1`` (quantile
    values) it consumes a ``(keys, weights)`` pair of global ``(m * b,)``
    arrays sharded the same way.  ``state``
    leaves that are per-site carry a leading ``m`` axis sharded over
    ``cfg.axis``; replicated leaves are replicated.

    Runners are cached on ``(protocol, cfg, mesh)``: protocol state is
    immutable and the step function pure, so same-shape tenants share one
    jitted callable (and its traces) instead of paying a retrace each.
    """
    from jax.sharding import PartitionSpec as P

    cfg = cfg.resolved()
    cached = _RUNNER_CACHE.get((protocol, cfg, mesh))
    if cached is not None:
        return cached
    init_fn = _INITS[protocol]
    step_fn = _STEPS[protocol]

    per_site_leaves = _PER_SITE_LEAVES[protocol]
    # HH and quantile streams arrive as a (keys/values, weights) pair of
    # 1-D arrays; matrix and leverage streams as one (n, d) row block.
    if protocol in ("HHP1", "QP1"):
        data_spec = (P(cfg.axis), P(cfg.axis))
    else:
        data_spec = P(cfg.axis, None)

    def _state_specs(state) -> object:
        specs = {}
        for name in state._fields:
            leaf = getattr(state, name)
            if name in per_site_leaves:
                spec = jax.tree.map(lambda _: P(cfg.axis), leaf)
            else:
                spec = jax.tree.map(lambda _: P(), leaf)
            specs[name] = spec
        return type(state)(**specs)

    def init_state():
        if protocol == "P3":
            one = init_fn(cfg)
            keys = jax.random.split(jax.random.key(0), cfg.m)
            state = one._replace(rng=keys)
        else:
            one = init_fn(cfg)

            def tile(name, leaf):
                if name in per_site_leaves:
                    return jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.m,) + a.shape), leaf)
                return leaf

            state = type(one)(**{n: tile(n, getattr(one, n)) for n in one._fields})
        return state

    def _inner(state, rows):
        # Inside shard_map: per-site leaves arrive with leading axis 1.
        def unbatch(name, leaf):
            if name in per_site_leaves:
                return jax.tree.map(lambda a: a[0], leaf)
            return leaf

        local = type(state)(**{n: unbatch(n, getattr(state, n)) for n in state._fields})
        new = step_fn(cfg, local, rows)

        def rebatch(name, leaf):
            if name in per_site_leaves:
                return jax.tree.map(lambda a: a[None], leaf)
            return leaf

        return type(new)(**{n: rebatch(n, getattr(new, n)) for n in new._fields})

    state0 = init_state()
    specs = _state_specs(state0)

    step = jax.jit(
        jax.shard_map(
            _inner,
            mesh=mesh,
            in_specs=(specs, data_spec),
            out_specs=specs,
            check_vma=False,
        )
    )
    _RUNNER_CACHE[(protocol, cfg, mesh)] = (state0, step)
    return state0, step


class PackedRunner(NamedTuple):
    """The two jitted entry points of one packed super-step program.

    ``stacked(stacked_state, rows)`` advances a resident ``(T, ...)``
    stacked state — the steady-state path: leaves stay on device in
    their pack layout between waves, nothing restacks.
    ``from_states(states_tuple, rows)`` additionally stacks a tuple of T
    per-tenant states inside the same jit first — the (re)pack path for
    a group's first wave or after a member stepped serially.  Both
    return the advanced *stacked* state; slice a tenant out lazily with
    ``unstack_packed`` only when its state is actually read.
    """

    stacked: Callable
    from_states: Callable


@functools.partial(jax.jit, static_argnums=1)
def unstack_packed(stacked_state, t: int):
    """Materialize tenant ``t``'s per-tenant state from a pack's stacked state.

    Jitted (one trace per (state structure, t)) so slicing a tenant out is
    ONE dispatch, not one per leaf — publish-heavy fleets read a member's
    state every wave, and an eager per-leaf tree.map would hand back most
    of the dispatch savings packing bought.
    """
    return jax.tree.map(lambda a: a[t], stacked_state)


def make_packed_runner(
    protocol: str, cfg: ProtocolConfig, mesh: jax.sharding.Mesh
) -> PackedRunner:
    """Return a ``PackedRunner`` advancing T tenants in one launch.

    The multi-tenant ingest megakernel: T per-tenant protocol states
    (same packable protocol, equal ``cfg``) stack along a leading tenant
    axis — per-site leaves become ``(T, m, ...)`` sharded
    ``P(None, axis)``, replicated leaves ``(T, ...)``, the tenants'
    zero-padded row batches one ``(T, n, d)`` block ``P(None, axis,
    None)`` — and a ``shard_map`` whose body ``vmap``s the per-site
    super-step over the tenant axis advances the whole pack in ONE
    dispatch (collectives batch over ``vmap``; the named site axis is
    orthogonal to the tenant axis).  The advanced state STAYS stacked:
    ``PackedRunner.stacked`` feeds it straight into the next wave with
    zero per-tenant host dispatches, and ``unstack_packed`` slices a
    tenant out only when something actually reads its state (publish,
    query, checkpoint) — restacking 14 leaves x T tenants per wave is
    what made an early packed path *slower* than serial on CPU.

    Ragged packs zero-pad each tenant's rows *per site block* up to the
    common ``n`` (see ``runtime.ingest_packed``); zero rows are exact
    no-ops for every ``PACKABLE_PROTOCOLS`` member, so the packed advance
    matches T serial ``make_protocol_runner`` steps on every served
    answer.  Cached on ``(protocol, cfg, mesh)`` like the serial runner
    (each jit retraces per distinct (T, n) launch shape).
    """
    from jax.sharding import PartitionSpec as P

    cfg = cfg.resolved()
    if protocol not in PACKABLE_PROTOCOLS:
        raise ValueError(
            f"protocol {protocol!r} is not packable; choose from {PACKABLE_PROTOCOLS}"
        )
    cached = _PACKED_RUNNER_CACHE.get((protocol, cfg, mesh))
    if cached is not None:
        return cached
    step_fn = _STEPS[protocol]
    per_site_leaves = _PER_SITE_LEAVES[protocol]
    one = _INITS[protocol](cfg)  # structure only: specs mirror the state tree

    def _specs(state) -> object:
        specs = {}
        for name in state._fields:
            leaf = getattr(state, name)
            if name in per_site_leaves:
                spec = jax.tree.map(lambda _: P(None, cfg.axis), leaf)
            else:
                spec = jax.tree.map(lambda _: P(), leaf)
            specs[name] = spec
        return type(state)(**specs)

    def _inner(state, rows):
        # Inside shard_map: per-site leaves arrive (T, 1, ...); drop the
        # site axis, vmap the per-site step over the tenant axis, rebatch.
        def unbatch(name, leaf):
            if name in per_site_leaves:
                return jax.tree.map(lambda a: a[:, 0], leaf)
            return leaf

        local = type(state)(**{n: unbatch(n, getattr(state, n)) for n in state._fields})
        new = jax.vmap(lambda st, r: step_fn(cfg, st, r))(local, rows)

        def rebatch(name, leaf):
            if name in per_site_leaves:
                return jax.tree.map(lambda a: a[:, None], leaf)
            return leaf

        return type(new)(**{n: rebatch(n, getattr(new, n)) for n in new._fields})

    specs = _specs(one)
    sharded = jax.shard_map(
        _inner,
        mesh=mesh,
        in_specs=(specs, P(None, cfg.axis, None)),
        out_specs=specs,
        check_vma=False,
    )

    @jax.jit
    def step_stacked(stacked, rows):
        return sharded(stacked, rows)

    @jax.jit
    def step_from_states(states, rows):
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        return sharded(stacked, rows)

    runner = PackedRunner(stacked=step_stacked, from_states=step_from_states)
    _PACKED_RUNNER_CACHE[(protocol, cfg, mesh)] = runner
    return runner
