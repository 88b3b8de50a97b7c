"""Weighted frequency sketches: Misra--Gries and SpaceSaving.

The paper's weighted-heavy-hitter protocols (Section 4) are built from a
*weighted* Misra--Gries (MG) summary: the weighted generalisation decrements
all counters by ``delta = min(min_counter, w)`` instead of by 1.  Guarantee
for ``k`` counters over total weight ``W``::

    0 <= f_e - hat{f}_e <= W / (k + 1)        (underestimates)

SpaceSaving is the overestimate twin (``0 <= hat{f}_e - f_e <= W / k``) the
paper cites [31] for bounding per-site space in protocols P2/P4.

Both come in two flavours:
  * ``MGState`` + ``mg_*`` — fixed-shape jit-able JAX arrays (production);
  * ``MGSketch`` / ``SpaceSaving`` — plain-python dict oracles used by the
    event-driven protocol engine and the tests.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MGState",
    "mg_init",
    "mg_update",
    "mg_update_stream",
    "mg_merge",
    "mg_estimate",
    "mg_items",
    "MGSketch",
    "SpaceSaving",
    "encode_hh_snapshot",
    "decode_hh_snapshot",
    "exact_heavy_hitters",
    "threshold_heavy_hitters",
]

EMPTY = jnp.int32(-1)


class MGState(NamedTuple):
    """Weighted Misra--Gries summary as fixed-shape jit-able arrays."""
    keys: jax.Array  # (k,) int32, -1 = empty
    counts: jax.Array  # (k,) f32
    weight: jax.Array  # () f32 — total weight consumed
    shrink: jax.Array  # () f32 — total decrement mass (error witness)


def mg_init(k: int) -> MGState:
    """The empty k-counter MG summary (the ``mg_merge`` identity)."""
    return MGState(
        keys=jnp.full((k,), EMPTY, jnp.int32),
        counts=jnp.zeros((k,), jnp.float32),
        weight=jnp.zeros((), jnp.float32),
        shrink=jnp.zeros((), jnp.float32),
    )


def mg_update(state: MGState, key: jax.Array, w: jax.Array) -> MGState:
    """Absorb one (element, weight) pair.  Fully branch-free / jit-able.

    A zero-weight item takes no slot, and a counter the decrement drives
    to zero frees its slot, so every live key has a positive count (the
    representation ``mg_merge`` produces, which makes the empty summary
    a bit-identical two-sided identity).
    """
    keys, counts = state.keys, state.counts
    key = key.astype(jnp.int32)
    w = w.astype(jnp.float32)

    hit = keys == key
    any_hit = jnp.any(hit)
    empty = keys == EMPTY
    # A zero-weight item takes no empty slot: it falls to case 3 with delta 0.
    any_empty = jnp.any(empty) & (w > 0.0)
    first_empty = jnp.argmax(empty)

    # Case 1: existing counter.
    counts_hit = counts + jnp.where(hit, w, 0.0)
    # Case 2: take an empty slot.
    keys_ins = keys.at[first_empty].set(key)
    counts_ins = counts.at[first_empty].set(w)
    # Case 3: decrement everyone by delta = min(min_count, w).
    min_c = jnp.min(counts)
    delta = jnp.minimum(min_c, w)
    counts_dec = jnp.maximum(counts - delta, 0.0)
    keys_dec = jnp.where(counts_dec > 0.0, keys, EMPTY)
    w_left = w - delta
    freed = jnp.argmin(counts)  # a slot that hit zero when delta == min_c
    take_slot = w_left > 0.0
    keys_dec = jnp.where(take_slot, keys_dec.at[freed].set(key), keys_dec)
    counts_dec = jnp.where(take_slot, counts_dec.at[freed].set(w_left), counts_dec)
    # shrink witness: every element's estimate dropped by at most delta
    # (the replaced slot loses min_c, the incoming item loses delta).

    new_keys = jnp.where(any_hit, keys, jnp.where(any_empty, keys_ins, keys_dec))
    new_counts = jnp.where(any_hit, counts_hit, jnp.where(any_empty, counts_ins, counts_dec))
    new_shrink = state.shrink + jnp.where(any_hit | any_empty, 0.0, delta)
    return MGState(new_keys, new_counts, state.weight + w, new_shrink)


def _mg_scan_body(st: MGState, kw):
    return mg_update(st, kw[0], kw[1]), None


@jax.jit
def mg_update_stream(state: MGState, keys: jax.Array, weights: jax.Array) -> MGState:
    """Scan ``mg_update`` over a (keys, weights) batch (compiled once per shape)."""
    state, _ = jax.lax.scan(
        _mg_scan_body, state, (keys.astype(jnp.int32), weights.astype(jnp.float32))
    )
    return state


def mg_estimate(state: MGState, key: jax.Array) -> jax.Array:
    """Estimated weight of ``key`` (0 when untracked; underestimates)."""
    hit = state.keys == key.astype(jnp.int32)
    return jnp.sum(jnp.where(hit, state.counts, 0.0))


def mg_items(state: MGState) -> dict[int, float]:
    """Materialize a jit-side MG summary as a plain ``{element: count}`` dict."""
    keys = np.asarray(state.keys)
    counts = np.asarray(state.counts)
    return {
        int(e): float(c) for e, c in zip(keys.tolist(), counts.tolist()) if e != int(EMPTY)
    }


def mg_merge(a: MGState, b: MGState) -> MGState:
    """Mergeable-summaries MG merge (Agarwal et al.): combine counts for equal
    keys, keep the k largest after subtracting the (k+1)-th largest."""
    k = a.keys.shape[0]
    keys = jnp.concatenate([a.keys, b.keys])
    counts = jnp.concatenate([a.counts, b.counts])
    valid = keys != EMPTY
    counts = jnp.where(valid, counts, 0.0)
    # Deduplicate: O((2k)^2), k is small (O(1/eps)).
    same = (keys[:, None] == keys[None, :]) & valid[:, None] & valid[None, :]
    summed = jnp.sum(jnp.where(same, counts[None, :], 0.0), axis=1)
    first = jnp.arange(2 * k) == jnp.argmax(same, axis=1)
    dedup = jnp.where(first & valid, summed, 0.0)
    # Keep top-k, subtract the (k+1)-th largest.
    order = jnp.argsort(-dedup)
    sorted_counts = dedup[order]
    thresh = sorted_counts[k]
    kept = jnp.maximum(sorted_counts[:k] - thresh, 0.0)
    kept_keys = jnp.where(kept > 0.0, keys[order[:k]], EMPTY)
    return MGState(
        keys=kept_keys,
        counts=kept,
        weight=a.weight + b.weight,
        shrink=a.shrink + b.shrink + thresh,
    )


# ---------------------------------------------------------------------------
# Python oracles (dict-based, exact event-driven semantics).
# ---------------------------------------------------------------------------


class MGSketch:
    """Weighted Misra--Gries over a python dict; error <= W/(k+1)."""

    def __init__(self, k: int):
        self.k = k
        self.counters: dict[int, float] = {}
        self.weight = 0.0
        self.shrink = 0.0

    def update(self, key: int, w: float) -> None:
        """Absorb one (element, weight) pair."""
        self.weight += w
        c = self.counters
        if key in c:
            c[key] += w
            return
        if len(c) < self.k:
            c[key] = w
            return
        delta = min(min(c.values()), w)
        self.shrink += delta
        dead = []
        for e in c:
            c[e] -= delta
            if c[e] <= 1e-12:
                dead.append(e)
        for e in dead:
            del c[e]
        if w - delta > 1e-12:
            c[key] = w - delta

    def extend(self, keys, weights) -> None:
        """Absorb a batch of (element, weight) pairs."""
        for key, w in zip(keys, weights):
            self.update(int(key), float(w))

    def estimate(self, key: int) -> float:
        """Estimated weight of ``key`` (0 when untracked; underestimates)."""
        return self.counters.get(key, 0.0)

    def merge(self, other: "MGSketch") -> None:
        """Fold another MG sketch in (mergeable-summaries merge)."""
        for e, w in other.counters.items():
            self.counters[e] = self.counters.get(e, 0.0) + w
        self.weight += other.weight
        self.shrink += other.shrink
        if len(self.counters) > self.k:
            vals = sorted(self.counters.values(), reverse=True)
            thresh = vals[self.k]
            self.shrink += thresh
            self.counters = {
                e: w - thresh for e, w in self.counters.items() if w - thresh > 1e-12
            }

    def items(self):
        """The live ``{element: count}`` counters (a copy)."""
        return dict(self.counters)

    def state_dict(self) -> dict:
        """JSON-able snapshot of the sketch (counter keys become strings)."""
        return {
            "k": self.k,
            "counters": {str(e): w for e, w in self.counters.items()},
            "weight": self.weight,
            "shrink": self.shrink,
        }

    @classmethod
    def from_state(cls, state: dict) -> "MGSketch":
        """Rebuild a sketch from ``state_dict`` output (exact state identity)."""
        mg = cls(int(state["k"]))
        mg.counters = {int(e): float(w) for e, w in state["counters"].items()}
        mg.weight = float(state["weight"])
        mg.shrink = float(state["shrink"])
        return mg


class SpaceSaving:
    """Weighted SpaceSaving; overestimates, error <= W/k."""

    def __init__(self, k: int):
        self.k = k
        self.counters: dict[int, float] = {}
        self.weight = 0.0

    def update(self, key: int, w: float) -> None:
        """Absorb one (element, weight) pair."""
        self.weight += w
        c = self.counters
        if key in c:
            c[key] += w
        elif len(c) < self.k:
            c[key] = w
        else:
            e_min = min(c, key=c.get)
            v_min = c.pop(e_min)
            c[key] = v_min + w

    def estimate(self, key: int) -> float:
        """Estimated weight of ``key`` (0 when untracked; overestimates)."""
        return self.counters.get(key, 0.0)

    def items(self):
        """The live ``{element: count}`` counters (a copy)."""
        return dict(self.counters)

    def state_dict(self) -> dict:
        """JSON-able snapshot of the sketch (counter keys become strings)."""
        return {
            "k": self.k,
            "counters": {str(e): w for e, w in self.counters.items()},
            "weight": self.weight,
        }

    @classmethod
    def from_state(cls, state: dict) -> "SpaceSaving":
        """Rebuild a sketch from ``state_dict`` output (exact state identity)."""
        ss = cls(int(state["k"]))
        ss.counters = {int(e): float(w) for e, w in state["counters"].items()}
        ss.weight = float(state["weight"])
        return ss


# ---------------------------------------------------------------------------
# Published-snapshot codec: HH estimates as a SketchStore matrix.
# ---------------------------------------------------------------------------


def encode_hh_snapshot(estimates: dict[int, float]) -> np.ndarray:
    """Pack coordinator HH estimates into a publishable ``(n, 2)`` f32 matrix.

    Column 0 holds element ids, column 1 their weight estimates, sorted by
    id so equal estimate sets encode bit-identically.  This is the matrix a
    ``SketchStore`` snapshot carries for an HH tenant (the store's contract
    is "one immutable 2-D array per version"); element ids must stay below
    2**24 so they survive the f32 round-trip exactly.
    """
    if not estimates:
        return np.zeros((0, 2), np.float32)
    if max(estimates) >= 1 << 24 or min(estimates) < 0:
        raise ValueError("HH element ids must be in [0, 2**24) to encode exactly as f32")
    pairs = sorted(estimates.items())
    return np.array(pairs, np.float32).reshape(len(pairs), 2)


def decode_hh_snapshot(matrix: np.ndarray) -> dict[int, float]:
    """Invert ``encode_hh_snapshot``: ``(n, 2)`` matrix back to an estimate dict."""
    m = np.asarray(matrix)
    if m.ndim != 2 or (m.size and m.shape[1] != 2):
        raise ValueError(f"HH snapshot matrix must be (n, 2), got {m.shape}")
    return {int(e): float(w) for e, w in m.tolist()}


def threshold_heavy_hitters(
    estimates: dict[int, float], w_hat: float, eps: float, phi: float
) -> list[int]:
    """The paper's Section 4 answer rule, shared by every query surface.

    Returns (sorted) every element whose estimate crosses
    ``(phi - eps/2) * w_hat`` — the threshold that guarantees no true
    phi-heavy-hitter is missed when estimates carry eps/2 error.  Live
    protocols, the registry interface, and published-snapshot queries must
    all apply this one implementation so their answers cannot diverge.
    """
    thr = (phi - eps / 2.0) * w_hat
    return sorted(e for e, v in estimates.items() if v >= thr)


def exact_heavy_hitters(keys: np.ndarray, weights: np.ndarray, phi: float):
    """Ground-truth phi-weighted heavy hitters of a finished stream."""
    totals: dict[int, float] = {}
    for k, w in zip(keys.tolist(), weights.tolist()):
        totals[k] = totals.get(k, 0.0) + w
    w_total = float(np.sum(weights))
    return {e: v for e, v in totals.items() if v >= phi * w_total}, totals, w_total
