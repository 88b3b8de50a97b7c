"""Frequent Directions (FD) sketch — the paper's core matrix substrate.

Two implementations, cross-validated in tests:

* ``FDState`` + ``fd_*`` functions — fixed-shape, jit-able JAX implementation
  (the production path; runs inside shard_map / scan on TPU).  Uses the
  Ghashami--Phillips fast variant: a ``2l x d`` buffer, shrinking back to at
  most ``l`` non-zero rows each time the buffer fills.  The shrink is computed
  with the Gram trick (``G = B @ B.T`` is ``2l x 2l``; ``eigh`` on it instead
  of an SVD of ``2l x d``), whose two matmul hot-spots map onto the Pallas
  kernels ``fd_gram`` / ``fd_project``.

* ``FDSketch`` — a plain-numpy, item-at-a-time oracle with the exact
  conditional-shrink semantics of the paper; used by the event-driven
  protocol engine and as the test oracle.

Guarantee (Liberty'13, as quoted in the paper):  for sketch parameter ``l``
and any unit vector ``x``::

    0 <= ||A x||^2 - ||B x||^2 <= delta_sum <= 2 ||A||_F^2 / l

where ``delta_sum`` is the accumulated shrink mass (tracked in the state, so
callers get the *instance-specific* bound, usually far tighter).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FDState",
    "fd_init",
    "fd_update",
    "fd_update_stream",
    "fd_merge",
    "fd_query",
    "fd_matrix",
    "fd_shrink",
    "jit_cache_stats",
    "FDSketch",
]


# Jitted FD callables, keyed on (op, l, d, dtype, use_pallas[, n_chunks]).
# Per-tenant ingest used to build a fresh trace per tracker instance for
# identical shapes; the cache makes the T-th tenant a dict hit.  ``misses``
# is the retrace count pipeline ingest observability surfaces.
_JIT_CACHE: dict = {}
_JIT_STATS = {"hits": 0, "misses": 0}


def jit_cache_stats() -> dict:
    """Counters for the shared jitted-callable cache.

    ``misses`` counts distinct (shape, dtype, backend) signatures traced —
    the retrace count; ``hits`` counts calls served by an already-built
    callable.  Read by ``StreamingPipeline.stats()``.
    """
    return dict(_JIT_STATS)


def _cached_jit(key: tuple, build):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        _JIT_STATS["misses"] += 1
        fn = _JIT_CACHE[key] = build()
    else:
        _JIT_STATS["hits"] += 1
    return fn


class FDState(NamedTuple):
    """Fixed-shape Frequent Directions sketch state.

    buf:       (2l, d) row buffer; rows [0, l) hold the current sketch, rows
               [l, 2l) are the staging area for incoming rows.
    frob:      () f32 — exact total squared Frobenius norm seen so far.
    delta_sum: () f32 — accumulated shrink mass; instance error bound.
    n_seen:    () i32 — number of rows consumed (excludes zero padding).
    """

    buf: jax.Array
    frob: jax.Array
    delta_sum: jax.Array
    n_seen: jax.Array

    @property
    def l(self) -> int:  # noqa: E743 - matches paper notation
        """Sketch row budget ``l`` (paper notation)."""
        return self.buf.shape[0] // 2

    @property
    def d(self) -> int:
        """Row dimensionality ``d``."""
        return self.buf.shape[1]


def fd_init(l: int, d: int, dtype=jnp.float32) -> FDState:
    """Create an empty sketch with parameter ``l`` (buffer holds ``2l`` rows)."""
    if l < 1:
        raise ValueError(f"FD sketch parameter l must be >= 1, got {l}")
    return FDState(
        buf=jnp.zeros((2 * l, d), dtype),
        frob=jnp.zeros((), jnp.float32),
        delta_sum=jnp.zeros((), jnp.float32),
        n_seen=jnp.zeros((), jnp.int32),
    )


def _gram(b: jax.Array, use_pallas: bool) -> jax.Array:
    # Default precision: one bf16 pass on a TPU, about 1e-3 relative to an
    # exact Gram.  The eps guarantee holds with it: on a TPU v5e the
    # largest error chip_smoke.py finds is 0.33 of its bound.
    if use_pallas:
        from repro.kernels import fd_ops

        return fd_ops.fd_gram(b)
    return jnp.matmul(b, b.T, preferred_element_type=jnp.float32)


def _project(w: jax.Array, u: jax.Array, b: jax.Array, use_pallas: bool) -> jax.Array:
    """Compute ``diag(w) @ (u.T @ b)`` — the FD shrink projection."""
    if use_pallas:
        from repro.kernels import fd_ops

        return fd_ops.fd_project(w, u, b)
    return (w[:, None] * jnp.matmul(u.T, b, preferred_element_type=jnp.float32)).astype(b.dtype)


def fd_shrink(buf: jax.Array, *, use_pallas: bool = False) -> tuple[jax.Array, jax.Array]:
    """One FD shrink of a full ``(2l, d)`` buffer.

    Returns ``(new_buf, delta)`` where ``new_buf`` has at most ``l`` non-zero
    rows (sorted by decreasing singular value) and ``delta`` is the shrink
    threshold ``sigma_l^2`` removed from every retained direction.
    """
    two_l, _ = buf.shape
    l = two_l // 2
    g = _gram(buf.astype(jnp.float32), use_pallas)
    # eigh: ascending eigenvalues.  Flip to descending.
    lam, u = jnp.linalg.eigh(g)
    lam = lam[::-1]
    u = u[:, ::-1]
    lam = jnp.maximum(lam, 0.0)
    delta = lam[l]  # (l+1)-th largest (0-indexed l) — the shrink mass
    new_sq = jnp.maximum(lam - delta, 0.0)
    # w_i = sqrt(new_sq_i / lam_i); safe where lam ~ 0 (row becomes zero).
    w = jnp.sqrt(new_sq / jnp.maximum(lam, 1e-30))
    w = jnp.where(lam > 1e-30, w, 0.0)
    new_buf = _project(w, u, buf.astype(jnp.float32), use_pallas).astype(buf.dtype)
    return new_buf, delta


def _fd_update_impl(state: FDState, chunk: jax.Array, *, use_pallas: bool) -> FDState:
    l = state.l
    row_sq = jnp.sum(chunk.astype(jnp.float32) ** 2, axis=1)
    buf = state.buf.at[l:].set(chunk.astype(state.buf.dtype))
    new_buf, delta = fd_shrink(buf, use_pallas=use_pallas)
    return FDState(
        buf=new_buf,
        frob=state.frob + jnp.sum(row_sq),
        delta_sum=state.delta_sum + delta,
        n_seen=state.n_seen + jnp.sum(row_sq > 0).astype(jnp.int32),
    )


def fd_update(state: FDState, chunk: jax.Array, *, use_pallas: bool = False) -> FDState:
    """Absorb a chunk of exactly ``l`` rows (zero-pad short chunks).

    Zero rows are free: they do not perturb the sketch and are excluded from
    ``frob`` / ``n_seen`` automatically (norm 0, count via non-zero test).
    The jitted callable is cached on ``(l, d, dtype, use_pallas)`` so every
    same-shape tenant shares one trace.
    """
    l = state.l
    if chunk.shape != (l, state.d):
        raise ValueError(f"fd_update wants a ({l}, {state.d}) chunk, got {chunk.shape}")
    fn = _cached_jit(
        ("update", l, state.d, str(state.buf.dtype), bool(use_pallas)),
        lambda: jax.jit(functools.partial(_fd_update_impl, use_pallas=use_pallas)),
    )
    return fn(state, chunk)


def _fd_stream_impl(state: FDState, chunks: jax.Array, *, use_pallas: bool) -> FDState:
    def body(st, ch):
        return _fd_update_impl(st, ch, use_pallas=use_pallas), None

    state, _ = jax.lax.scan(body, state, chunks)
    return state


def fd_update_stream(state: FDState, rows: jax.Array, *, use_pallas: bool = False) -> FDState:
    """Absorb ``(n, d)`` rows via a scan of l-row chunks (n padded up).

    The jitted scan is cached on ``(l, d, dtype, use_pallas, n_chunks)`` —
    per-tenant ingest of a common batch shape stops re-tracing per tenant.
    """
    l, d = state.l, state.d
    n = rows.shape[0]
    n_chunks = -(-n // l)
    pad = n_chunks * l - n
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    chunks = rows.reshape(n_chunks, l, d)
    fn = _cached_jit(
        ("stream", l, d, str(state.buf.dtype), bool(use_pallas), n_chunks),
        lambda: jax.jit(functools.partial(_fd_stream_impl, use_pallas=use_pallas)),
    )
    return fn(state, chunks)


def fd_merge(a: FDState, b: FDState, *, use_pallas: bool = False) -> FDState:
    """Merge two sketches (mergeable-summaries property, used by protocol P1).

    Stacks the <=l live rows of each into one 2l buffer and shrinks once.
    Error bounds add: delta_sum_merged <= delta_a + delta_b + delta_shrink.
    """
    l = a.l
    if b.l != l or b.d != a.d:
        raise ValueError("fd_merge requires identically-shaped sketches")
    buf = jnp.concatenate([a.buf[:l], b.buf[:l]], axis=0)
    new_buf, delta = fd_shrink(buf, use_pallas=use_pallas)
    return FDState(
        buf=new_buf,
        frob=a.frob + b.frob,
        delta_sum=a.delta_sum + b.delta_sum + delta,
        n_seen=a.n_seen + b.n_seen,
    )


def fd_query(state: FDState, x: jax.Array) -> jax.Array:
    """``||B x||^2`` — the paper's tracked quantity, for unit direction x."""
    return jnp.sum(jnp.matmul(state.buf, x, preferred_element_type=jnp.float32) ** 2, axis=0)


def fd_matrix(state: FDState) -> jax.Array:
    """The sketch matrix B (l x d): the live rows of the buffer."""
    return state.buf[: state.l]


# ---------------------------------------------------------------------------
# Numpy oracle — exact item-at-a-time semantics for the event-driven engine.
# ---------------------------------------------------------------------------


class FDSketch:
    """Plain-numpy Frequent Directions with per-item conditional shrink.

    This is the paper's algorithm verbatim: rows are appended one at a time
    into the first empty slot; when the buffer fills, shrink.  Used as the
    oracle for the JAX implementation and as the site/coordinator sketch in
    the event-driven protocol engine.
    """

    def __init__(self, l: int, d: int):
        self.l = l
        self.d = d
        self.buf = np.zeros((2 * l, d), np.float64)
        self.fill = 0
        self.frob = 0.0
        self.delta_sum = 0.0
        self.n_seen = 0

    def append(self, row: np.ndarray) -> None:
        """Absorb one stream row (shrinks when the buffer fills)."""
        if self.fill == self.buf.shape[0]:
            self._shrink()
        self.buf[self.fill] = row
        self.fill += 1
        self.frob += float(row @ row)
        self.n_seen += 1

    def extend(self, rows: np.ndarray) -> None:
        # Vectorized fast path: fill in slabs, shrink when full.
        """Absorb an (n, d) batch of rows."""
        i = 0
        n = rows.shape[0]
        self.frob += float(np.sum(rows * rows))
        self.n_seen += n
        while i < n:
            if self.fill == self.buf.shape[0]:
                self._shrink()
            take = min(n - i, self.buf.shape[0] - self.fill)
            self.buf[self.fill : self.fill + take] = rows[i : i + take]
            self.fill += take
            i += take

    def _shrink(self) -> None:
        g = self.buf @ self.buf.T
        lam, u = np.linalg.eigh(g)
        lam = np.maximum(lam[::-1], 0.0)
        u = u[:, ::-1]
        delta = lam[self.l]
        w = np.sqrt(np.maximum(lam - delta, 0.0) / np.maximum(lam, 1e-300))
        w[lam <= 1e-300] = 0.0
        self.buf = (w[:, None] * (u.T @ self.buf))
        self.delta_sum += float(delta)
        self.fill = self.l

    def matrix(self) -> np.ndarray:
        """Current sketch rows (fill x d)."""
        return self.buf[: self.fill]

    def query(self, x: np.ndarray) -> float:
        """``||B x||^2`` — the sketch's estimate of ``||A x||^2``."""
        v = self.buf[: self.fill] @ x
        return float(v @ v)

    def merge(self, other: "FDSketch") -> None:
        """Fold another FD sketch in (mergeable-summaries merge)."""
        self.extend(other.matrix())
        # extend() already added other's frob/n via rows; but rows of a sketch
        # under-count the true stream mass — correct with other's bookkeeping.
        self.frob += other.frob - float(np.sum(other.matrix() ** 2))
        self.n_seen += other.n_seen - other.matrix().shape[0]
        self.delta_sum += other.delta_sum

    def covariance_error(self, a: np.ndarray) -> float:
        """``||A^T A - B^T B||_2 / ||A||_F^2`` — the paper's err metric."""
        b = self.matrix()
        m = a.T @ a - b.T @ b
        return float(np.linalg.norm(m, 2) / max(np.sum(a * a), 1e-300))

    # -- persistence ---------------------------------------------------------

    def state_dict(self) -> dict:
        """JSON-able snapshot of the sketch (exact float round-trip).

        The checkpoint convention every sketch in ``core`` follows
        (``MGSketch``, ``QuantileSummary``): streams that embed an
        ``FDSketch`` persist it through this, so a future field change
        cannot silently miss an out-of-module serializer.
        """
        return {
            "buf": self.buf.tolist(),
            "fill": self.fill,
            "frob": self.frob,
            "delta_sum": self.delta_sum,
            "n_seen": self.n_seen,
        }

    @classmethod
    def from_state(cls, state: dict, l: int, d: int) -> "FDSketch":
        """Rebuild a sketch from ``state_dict`` output (state identity)."""
        fd = cls(l, d)
        fd.buf = np.asarray(state["buf"], np.float64)
        fd.fill = int(state["fill"])
        fd.frob = float(state["frob"])
        fd.delta_sum = float(state["delta_sum"])
        fd.n_seen = int(state["n_seen"])
        return fd
