"""Jit'd wrappers for the FD Pallas kernels (padding + backend dispatch).

``interpret`` defaults to True off-TPU so the same call sites work in this
CPU container and on real hardware.  Padding: L to a multiple of 8 (f32
sublane), d to a multiple of the d-block.  Zero rows/cols are exact no-ops
for both kernels.

``path`` follows the ``ops.levscore`` convention: ``auto`` routes to the
Pallas kernel on a real accelerator and to the jit'd XLA reference wherever
the kernel would run in interpret mode (interpreted Pallas loses to XLA on
CPU); ``"pallas"`` / ``"xla"`` force one implementation.  Both paths agree
to 1e-5 on CPU (regression-tested) and bit for bit on a TPU v5e, where
both multiply f32 in one bf16 pass (see ``kernels.ops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fd_gram import DEFAULT_BLOCK_D, fd_gram_pallas
from repro.kernels.fd_project import fd_project_pallas

__all__ = ["FD_PATHS", "fd_gram", "fd_project"]

FD_PATHS = ("auto", "pallas", "xla")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _use_xla(path: str, interpret: bool | None, which: str) -> tuple[bool, bool]:
    """Resolve (use_xla, interpret) for one call under the shared convention."""
    if path not in FD_PATHS:
        raise ValueError(f"unknown {which} path {path!r}; choose from {FD_PATHS}")
    if interpret is None:
        interpret = not _on_tpu()
    return path == "xla" or (path == "auto" and interpret), interpret


@jax.jit
def _gram_xla(b):
    from repro.kernels.ref import ref_fd_gram

    return ref_fd_gram(b)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def _gram_padded(b, *, block_d, interpret):
    return fd_gram_pallas(b, block_d=block_d, interpret=interpret)


def fd_gram(
    b: jax.Array,
    *,
    block_d: int = 0,
    interpret: bool | None = None,
    path: str = "auto",
) -> jax.Array:
    """``B @ B.T`` (f32), backend-dispatched, any (L, d)."""
    use_xla, interpret = _use_xla(path, interpret, "fd_gram")
    if use_xla:
        return _gram_xla(b)
    l, d = b.shape
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(l, 8), 8)
    dp = _pad_to(d, block_d)
    bp = jnp.pad(b, ((0, lp - l), (0, dp - d)))
    g = _gram_padded(bp, block_d=block_d, interpret=interpret)
    return g[:l, :l]


@jax.jit
def _project_xla(w, u, b):
    from repro.kernels.ref import ref_fd_project

    return ref_fd_project(w, u, b)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def _project_padded(w, u, b, *, block_d, interpret):
    return fd_project_pallas(w, u, b, block_d=block_d, interpret=interpret)


def fd_project(
    w: jax.Array,
    u: jax.Array,
    b: jax.Array,
    *,
    block_d: int = 0,
    interpret: bool | None = None,
    path: str = "auto",
) -> jax.Array:
    """``diag(w) @ (U.T @ B)``, backend-dispatched, any (L,), (L,L), (L,d)."""
    use_xla, interpret = _use_xla(path, interpret, "fd_project")
    if use_xla:
        return _project_xla(w, u, b)
    l, d = b.shape
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(l, 8), 8)
    dp = _pad_to(d, block_d)
    wp = jnp.pad(w, (0, lp - l))
    up = jnp.pad(u, ((0, lp - l), (0, lp - l)))
    bp = jnp.pad(b, ((0, lp - l), (0, dp - d)))
    out = _project_padded(wp, up, bp, block_d=block_d, interpret=interpret)
    return out[:l, :d]
