"""Jit'd public entry points for all Pallas kernels.

* ``fd_gram`` / ``fd_project`` — FD shrink hot-spots (see fd_ops.py).
* ``fd_shrink`` / ``fd_spectra`` — batched-over-tenants FD shrink and
  spectrum refresh (see fd_shrink_fused.py); one launch per stage serves a
  whole ``(T, 2l, d)`` pack.
* ``flash_attention``         — causal/GQA/windowed attention; pads seq to
  block multiples (padded key rows are masked out by causality + explicit
  length masking, padded q rows are dropped).

Backend dispatch convention (``path="auto"|"pallas"|"xla"``): ``auto``
routes to the fused Pallas kernel on a real accelerator and to the jit'd
XLA reference wherever the kernel would run in interpret mode — on CPU the
interpreted kernel measurably loses to XLA.  ``auto`` decides from
``jax.default_backend()`` alone; ``chip_smoke.py`` checks on the chip that
the compiled programs really hold the kernels.

Precision: both paths multiply f32 at default precision.  On a TPU that is
one bf16 pass in the kernels (Mosaic's default) and in XLA alike, about
1e-3 relative to an exact product, well inside every eps bound the
served answers carry.  Measured on a TPU v5e, the two paths agree bit for
bit on ``quadform``/``quadform_packed``/``fd_spectra``/``fd_gram`` and to
3.4e-7 on ``levscore``; on CPU regression tests pin them to 1e-5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fd_ops import fd_gram, fd_project
from repro.kernels.fd_shrink_fused import (
    fd_gram_batched_pallas,
    fd_project_batched_pallas,
)
from repro.kernels.flash_attention import (
    DEFAULT_BLOCK_KV,
    DEFAULT_BLOCK_Q,
    flash_attention_pallas,
)
from repro.kernels.levscore import levscore_pallas
from repro.kernels.quadform import (
    DEFAULT_BLOCK_D,
    DEFAULT_BLOCK_N,
    quadform_pallas,
    quadform_packed_pallas,
)

__all__ = [
    "fd_gram",
    "fd_project",
    "fd_shrink",
    "fd_spectra",
    "flash_attention",
    "levscore",
    "quadform",
    "quadform_packed",
]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def _quadform_padded(b, x, *, block_n, block_d, interpret):
    return quadform_pallas(b, x, block_n=block_n, block_d=block_d, interpret=interpret)


def quadform(
    b: jax.Array,
    x: jax.Array,
    *,
    block_n: int = 0,
    block_d: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``||B x_j||^2`` via the Pallas kernel, any (L, d) x (N, d) -> (N,).

    Pads L to the f32 sublane multiple and N/d to block multiples; zero
    rows/cols contribute zero to every dot product, so padding is exact.
    """
    if interpret is None:
        interpret = not _on_tpu()
    l, d = b.shape
    n = x.shape[0]
    if block_n <= 0:
        block_n = min(DEFAULT_BLOCK_N, _pad_to(n, 128))
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(l, 8), 8)
    dp = _pad_to(d, block_d)
    np_ = _pad_to(max(n, block_n), block_n)
    bp = jnp.pad(b, ((0, lp - l), (0, dp - d)))
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    out = _quadform_padded(bp, xp, block_n=block_n, block_d=block_d, interpret=interpret)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def _quadform_packed_padded(b, x, *, block_n, block_d, interpret):
    return quadform_packed_pallas(b, x, block_n=block_n, block_d=block_d, interpret=interpret)


def quadform_packed(
    b: jax.Array,
    x: jax.Array,
    *,
    block_n: int = 0,
    block_d: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Cross-tenant packed ``||B_t x_tj||^2``: (T, L, d) x (T, N, d) -> (T, N).

    One Pallas launch serves every tenant in the pack (vs T separate
    ``quadform`` dispatches).  Padding rules match ``quadform``; zero pad
    rows/cols are exact no-ops, so ragged per-tenant query counts can be
    zero-padded up to a shared N.
    """
    if interpret is None:
        interpret = not _on_tpu()
    t, l, d = b.shape
    n = x.shape[1]
    if block_n <= 0:
        block_n = min(DEFAULT_BLOCK_N, _pad_to(n, 128))
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(l, 8), 8)
    dp = _pad_to(d, block_d)
    np_ = _pad_to(max(n, block_n), block_n)
    bp = jnp.pad(b, ((0, 0), (0, lp - l), (0, dp - d)))
    xp = jnp.pad(x, ((0, 0), (0, np_ - n), (0, dp - d)))
    out = _quadform_packed_padded(bp, xp, block_n=block_n, block_d=block_d, interpret=interpret)
    return out[:, 0, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def _levscore_padded(m, x, *, block_n, block_d, interpret):
    return levscore_pallas(m, x, block_n=block_n, block_d=block_d, interpret=interpret)


@jax.jit
def _levscore_xla(m, x):
    from repro.kernels.ref import ref_levscore

    return ref_levscore(m, x)


LEVSCORE_PATHS = ("auto", "pallas", "xla")


def levscore(
    m: jax.Array,
    x: jax.Array,
    *,
    block_n: int = 0,
    block_d: int = 0,
    interpret: bool | None = None,
    path: str = "auto",
) -> jax.Array:
    """Batched ``x_j^T M x_j``, backend-dispatched, (d, d) x (N, d) -> (N,).

    ``path="auto"`` picks per backend: the fused Pallas kernel on a real
    accelerator, the jit'd XLA reference contraction wherever the kernel
    would run in interpret mode — on CPU the interpreted kernel
    measurably *loses* to XLA (BENCH_leverage_protocols.json: ~100ms vs
    ~9ms for the same sweep), so falling back is the fast path; the
    paths agree to 1e-5 on CPU and 3.4e-7 on a TPU v5e (module docstring).  ``path="pallas"`` /
    ``"xla"`` force one implementation (kernel tests, benchmarks).

    The Pallas path pads N/d to block multiples; zero pad rows/cols of M
    and X contribute zero to every quadratic form, so padding is exact.
    """
    if path not in LEVSCORE_PATHS:
        raise ValueError(f"unknown levscore path {path!r}; choose from {LEVSCORE_PATHS}")
    if interpret is None:
        interpret = not _on_tpu()
    if path == "xla" or (path == "auto" and interpret):
        return _levscore_xla(m, x)
    d = m.shape[0]
    n = x.shape[0]
    if block_n <= 0:
        block_n = min(DEFAULT_BLOCK_N, _pad_to(max(n, 1), 128))
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    dp = _pad_to(d, block_d)
    np_ = _pad_to(max(n, block_n), block_n)
    mp = jnp.pad(m, ((0, dp - d), (0, dp - d)))
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    out = _levscore_padded(mp, xp, block_n=block_n, block_d=block_d, interpret=interpret)
    return out[0, :n]


FD_SHRINK_PATHS = ("auto", "pallas", "xla")


@jax.jit
def _fd_shrink_xla(b):
    from repro.kernels.ref import ref_fd_shrink

    return ref_fd_shrink(b)


@functools.partial(jax.jit, static_argnames=("half", "block_d", "interpret"))
def _fd_shrink_fused(b, *, half, block_d, interpret):
    g = fd_gram_batched_pallas(b, block_d=block_d, interpret=interpret)
    lam, u = jnp.linalg.eigh(g)  # batched over T; ascending
    lam = jnp.maximum(jnp.flip(lam, axis=-1), 0.0)
    u = jnp.flip(u, axis=-1)
    delta = lam[:, half]
    w = jnp.sqrt(jnp.maximum(lam - delta[:, None], 0.0) / jnp.maximum(lam, 1e-30))
    w = jnp.where(lam <= 1e-30, 0.0, w)
    out = fd_project_batched_pallas(w, u, b, block_d=block_d, interpret=interpret)
    return out, delta


def fd_shrink(
    b: jax.Array,
    *,
    block_d: int = 0,
    interpret: bool | None = None,
    path: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Batched FD shrink: (T, 2l, d) -> (B' (T, 2l, d), delta (T,)).

    One fused pipeline shrinks every tenant in a stacked pack: a single
    batched Gram launch, ONE batched ``eigh`` over the (T, 2l, 2l) Grams,
    and a single batched projection launch with the ``diag(w)`` rescale
    fused into the matmul epilogue — versus 3T dispatches for a Python
    loop of per-tenant ``fd_shrink`` calls.  Numerics match
    ``core.fd.fd_shrink`` row for row; also accepts an unstacked (2l, d)
    buffer (returns ((2l, d), ()) like the core routine).

    ``path`` follows the ``levscore`` dispatch convention: ``auto`` uses
    the Pallas kernels on a real accelerator and the jit'd XLA reference
    in interpret mode (where interpreted Pallas loses on CPU); both agree
    as the module docstring states.  Pallas padding (2l to the f32 sublane multiple, d to the
    d-block) is exact: padded zero rows add zero eigenvalues, which sort
    past the shrink threshold and get weight zero.
    """
    if path not in FD_SHRINK_PATHS:
        raise ValueError(f"unknown fd_shrink path {path!r}; choose from {FD_SHRINK_PATHS}")
    if interpret is None:
        interpret = not _on_tpu()
    if path == "xla" or (path == "auto" and interpret):
        return _fd_shrink_xla(b)
    squeeze = b.ndim == 2
    bs = b[None] if squeeze else b
    _, two_l, d = bs.shape
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(two_l, 8), 8)
    dp = _pad_to(d, block_d)
    bp = jnp.pad(bs, ((0, 0), (0, lp - two_l), (0, dp - d)))
    out, delta = _fd_shrink_fused(bp, half=two_l // 2, block_d=block_d, interpret=interpret)
    out = out[:, :two_l, :d]
    if squeeze:
        return out[0], delta[0]
    return out, delta


@jax.jit
def _fd_spectra_xla(b):
    from repro.kernels.ref import ref_fd_spectra

    return ref_fd_spectra(b)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def _fd_spectra_fused(b, *, block_d, interpret):
    g = fd_gram_batched_pallas(b, block_d=block_d, interpret=interpret)
    lam, u = jnp.linalg.eigh(g)
    lam = jnp.maximum(jnp.flip(lam, axis=-1), 0.0)
    u = jnp.flip(u, axis=-1)
    s = jnp.sqrt(lam)
    tol = s[:, :1] * 1e-7
    w = jnp.where(s > tol, 1.0 / jnp.maximum(s, 1e-30), 0.0)
    vt = fd_project_batched_pallas(w, u, b, block_d=block_d, interpret=interpret)
    return s, vt


def fd_spectra(
    b: jax.Array,
    *,
    block_d: int = 0,
    interpret: bool | None = None,
    path: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Batched sketch spectra: (T, l, d) -> (s (T, l), vt (T, l, d)).

    The publish-time spectrum refresh: one batched Gram + ONE batched
    ``eigh`` + one batched projection recover every stacked sketch's
    singular values (descending) and right singular directions — the same
    ``(s, vt)`` pair ``QueryEngine``'s per-snapshot SVD produces, up to
    per-row sign (irrelevant to every served quantity, which squares the
    projections).  Rows whose singular value is below ``1e-7 * s_max``
    come back zero instead of noise.  ``path`` dispatches like
    ``fd_shrink``; requires l <= d (thin spectra).
    """
    if path not in FD_SHRINK_PATHS:
        raise ValueError(f"unknown fd_spectra path {path!r}; choose from {FD_SHRINK_PATHS}")
    if interpret is None:
        interpret = not _on_tpu()
    if b.ndim != 3 or b.shape[1] > b.shape[2]:
        raise ValueError(f"fd_spectra wants stacked (T, l, d) with l <= d, got {b.shape}")
    if path == "xla" or (path == "auto" and interpret):
        return _fd_spectra_xla(b)
    _, l, d = b.shape
    if block_d <= 0:
        block_d = min(DEFAULT_BLOCK_D, _pad_to(d, 128))
    lp = _pad_to(max(l, 8), 8)
    dp = _pad_to(d, block_d)
    bp = jnp.pad(b, ((0, 0), (0, lp - l), (0, dp - d)))
    s, vt = _fd_spectra_fused(bp, block_d=block_d, interpret=interpret)
    return s[:, :l], vt[:, :l, :d]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "logit_softcap", "block_q", "block_kv", "interpret"),
)
def _flash_padded(q, k, v, *, causal, window, scale, logit_softcap, block_q, block_kv, interpret):
    return flash_attention_pallas(
        q,
        k,
        v,
        causal=causal,
        window=window,
        scale=scale,
        logit_softcap=logit_softcap,
        block_q=block_q,
        block_kv=block_kv,
        interpret=interpret,
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    logit_softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
) -> jax.Array:
    """Self-attention (sq == skv) with seq padding to block multiples.

    Padded *key* positions sit at the end of the stream; causal masking plus
    the zero-query trick keeps them out of every real row's softmax.
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, hq, s, dh = q.shape
    if scale is None:
        scale = dh**-0.5
    block_q = min(block_q, _pad_to(s, 128))
    block_kv = min(block_kv, _pad_to(s, 128))
    sp = _pad_to(s, max(block_q, block_kv))
    if sp != s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    out = _flash_padded(
        q,
        k,
        v,
        causal=causal,
        window=window,
        scale=scale,
        logit_softcap=logit_softcap,
        block_q=block_q,
        block_kv=block_kv,
        interpret=interpret,
    )
    return out[:, :, :s, :]
