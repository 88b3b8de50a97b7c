"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contracts).

Tests sweep shapes/dtypes and assert the kernels (interpret=True on CPU)
match these to tight tolerances.  Products take f32 inputs and accumulate
in f32 at default precision, which on a TPU is one bf16 pass — the same
pass the Pallas kernels make there (see ``kernels.ops``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_fd_gram(b: jax.Array) -> jax.Array:
    """FD Gram product ``G = B @ B.T``, f32 out.  b: (L, d) -> (L, L)."""
    b32 = b.astype(jnp.float32)
    return jnp.matmul(b32, b32.T, preferred_element_type=jnp.float32)


def ref_fd_project(w: jax.Array, u: jax.Array, b: jax.Array) -> jax.Array:
    """FD shrink projection ``diag(w) @ (U.T @ B)``.

    w: (L,), u: (L, L), b: (L, d) -> (L, d) in b's dtype.
    """
    out = w[:, None].astype(jnp.float32) * jnp.matmul(
        u.astype(jnp.float32).T, b.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    return out.astype(b.dtype)


def ref_fd_gram_batched(b: jax.Array) -> jax.Array:
    """Stacked FD Grams ``G_t = B_t @ B_t.T``.  b: (T, L, d) -> (T, L, L)."""
    return jax.vmap(ref_fd_gram)(b)


def ref_fd_project_batched(w: jax.Array, u: jax.Array, b: jax.Array) -> jax.Array:
    """Stacked shrink projections ``diag(w_t) @ (U_t.T @ B_t)``.

    w: (T, L), u: (T, L, L), b: (T, L, d) -> (T, L, d) in b's dtype.
    """
    return jax.vmap(ref_fd_project)(w, u, b)


def ref_fd_shrink(b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One full FD shrink of a stacked buffer: (T, 2l, d) -> (B', delta).

    The oracle for ``ops.fd_shrink``: Gram -> eigh (descending) -> clamp ->
    ``delta_t = lam_t[l]`` -> guarded ``w`` -> projection, all batched over
    the leading tenant axis.  Returns ``(B', delta)`` with B' (T, 2l, d)
    and delta (T,) f32.  Also accepts unstacked (2l, d) -> ((2l, d), ()).
    """
    squeeze = b.ndim == 2
    bs = b[None] if squeeze else b
    g = ref_fd_gram_batched(bs)
    lam, u = jnp.linalg.eigh(g)  # ascending
    lam = jnp.flip(lam, axis=-1)
    u = jnp.flip(u, axis=-1)
    lam = jnp.maximum(lam, 0.0)
    half = bs.shape[1] // 2
    delta = lam[:, half]
    shifted = jnp.maximum(lam - delta[:, None], 0.0)
    w = jnp.sqrt(shifted / jnp.maximum(lam, 1e-30))
    w = jnp.where(lam <= 1e-30, 0.0, w)
    out = ref_fd_project_batched(w, u, bs)
    if squeeze:
        return out[0], delta[0]
    return out, delta


def ref_fd_spectra(b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Stacked sketch spectra via the Gram trick: (T, l, d) -> (s, vt).

    The oracle for ``ops.fd_spectra``: ``s`` (T, l) descending singular
    values, ``vt`` (T, l, d) right singular directions (rows below
    ``1e-7 * s_max`` zeroed).  Matches a per-matrix SVD up to per-row sign.
    """
    g = ref_fd_gram_batched(b)
    lam, u = jnp.linalg.eigh(g)
    lam = jnp.maximum(jnp.flip(lam, axis=-1), 0.0)
    u = jnp.flip(u, axis=-1)
    s = jnp.sqrt(lam)
    tol = s[:, :1] * 1e-7
    w = jnp.where(s > tol, 1.0 / jnp.maximum(s, 1e-30), 0.0)
    vt = ref_fd_project_batched(w, u, b)
    return s, vt


def ref_levscore(m: jax.Array, x: jax.Array) -> jax.Array:
    """Batched quadratic form ``tau_j = x_j^T M x_j``.  m: (d, d), x: (N, d) -> (N,)."""
    xf = x.astype(jnp.float32)
    xm = jnp.matmul(xf, m.astype(jnp.float32), preferred_element_type=jnp.float32)
    return jnp.sum(xm * xf, axis=1)


def ref_quadform(b: jax.Array, x: jax.Array) -> jax.Array:
    """Batched quadratic form ``q_j = ||B x_j||^2``.  b: (L, d), x: (N, d) -> (N,)."""
    bx = jnp.matmul(
        b.astype(jnp.float32), x.astype(jnp.float32).T, preferred_element_type=jnp.float32
    )
    return jnp.sum(bx * bx, axis=0)


def ref_quadform_packed(b: jax.Array, x: jax.Array) -> jax.Array:
    """Packed form: b (T, L, d), x (T, N, d) -> (T, N); row t uses sketch t."""
    return jax.vmap(ref_quadform)(b, x)


def ref_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    logit_softcap: float = 0.0,
) -> jax.Array:
    """Reference multi-head attention with GQA + sliding window.

    q: (b, hq, sq, dh); k, v: (b, hkv, skv, dh).  hq % hkv == 0.
    ``window`` > 0 masks keys further than ``window`` positions behind the
    query (sliding-window attention); 0 means unlimited.
    Query position i attends key positions [max(0, i+off-window+1), i+off]
    where off = skv - sq (decode-style alignment: queries are the last sq
    positions of the key stream).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = dh**-0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # Expand kv heads to q heads.
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    off = skv - sq
    qpos = jnp.arange(sq)[:, None] + off
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)
