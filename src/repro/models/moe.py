"""Token-choice top-k MoE with sort-based dispatch (EP over the model axis).

FLOP-faithful MoE: only routed tokens hit expert weights.  The dispatch is
the sort-based formulation (no (T, E, C) one-hot blow-up):

  1. route: softmax(x @ Wr) -> top-k (gates, expert ids) per token
  2. sort the T*K (token, choice) pairs by expert id
  3. per-pair queue position via searchsorted; drop beyond capacity C
  4. scatter token activations into an (E, C, d) buffer   <- all_to_all
     under EP sharding (E sharded over "model")
  5. batched expert FFN: einsum over the stacked (E, d, ff) weights
  6. gather back and combine with gates                   <- all_to_all back

Capacity C = ceil(T * K / E * capacity_factor); dropped tokens pass through
the residual (standard GShard semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dtype_of, linear_init


def _eff_dims(cfg):
    """Effective (virtual) expert grid: e*v experts of ff/v width each."""
    v = max(cfg.moe_virtual_split, 1)
    return cfg.n_experts * v, cfg.experts_per_token * v, cfg.d_ff // v, v


def moe_init(key, cfg) -> dict:
    d, e = cfg.d_model, cfg.n_experts
    e_v, _, ff_v, _ = _eff_dims(cfg)
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": linear_init(k1, d, e, jnp.float32),
        "w_gate": (jax.random.normal(k2, (e_v, d, ff_v), jnp.float32) * d**-0.5).astype(dt),
        "w_up": (jax.random.normal(k3, (e_v, d, ff_v), jnp.float32) * d**-0.5).astype(dt),
        "w_down": (jax.random.normal(k4, (e_v, ff_v, d), jnp.float32) * cfg.d_ff**-0.5).astype(dt),
    }


def _virtualize_routing(cfg, gates, expert_idx):
    """(.., k) real-expert choices -> (.., k*v) virtual-expert choices.
    Each half receives the full gate; their down-proj outputs add."""
    _, _, _, v = _eff_dims(cfg)
    if v == 1:
        return gates, expert_idx
    idx = (expert_idx[..., None] * v + jnp.arange(v)).reshape(*expert_idx.shape[:-1], -1)
    g = jnp.repeat(gates, v, axis=-1)
    return g, idx


def moe_apply(cfg, params: dict, x: jax.Array) -> jax.Array:
    """x: (B, T, D) -> (B, T, D).

    The routing/sort/scatter runs *per data-parallel group*: tokens are
    reshaped to (G, n/G, D) with G = the DP degree, and the whole dispatch
    is vmapped over G.  Every dispatch op is then batch-parallel over a
    DP-sharded axis, so XLA executes it without cross-shard communication —
    the only collective left is the intended dispatch/combine all-to-all of
    the expert einsums (EP over the "model" axis).  This is what keeps the
    1M-token qwen3-moe train step from global-sorting 8M routing keys.
    """
    from repro.models import sharding as shd

    b, t, d = x.shape
    n = b * t
    ctx = shd.current_ctx()
    e_v, _, _, _ = _eff_dims(cfg)
    if (
        ctx is not None
        and n > 512
        and ctx["dp_size"] > 1
        and b % ctx["dp_size"] == 0
        and ctx["model_size"] > 1
        and e_v % ctx["model_size"] == 0
    ):
        return _moe_shard_map(cfg, params, x, ctx)
    g = shd.current_dp_size()
    if n > 512 and g > 1 and b % g == 0:
        xg = shd.constrain_moe_tokens(x.reshape(g, n // g, d))
        out = _moe_grouped(cfg, params, xg)
        return out.reshape(b, t, d)
    return _moe_flat(cfg, params, x.reshape(n, d)).reshape(b, t, d)


def _moe_shard_map(cfg, params: dict, x: jax.Array, ctx) -> jax.Array:
    """Manual expert parallelism: tokens DP-local, experts model-sharded.

    Each (data, model) shard routes its *local* tokens (replicated routing
    along the model axis — deterministic), dispatches only the entries bound
    for its own expert slice, runs the local expert FFNs, scatters back and
    psums partial token outputs over the model axis.  The only collectives
    are the entry all-gather (sequence-parallel boundary, inserted by XLA)
    and one (n_local, d) psum — no global sorts, no capacity-bloated
    all-reduces.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    e_v, k_v, ff_v, _ = _eff_dims(cfg)
    msize = ctx["model_size"]
    e_loc = e_v // msize
    dp = ctx["dp"]
    mdl = "model"
    b, t, d = x.shape

    def inner(xb, router, wg, wu, wd):
        # xb: (b_loc, t, d); wg/wu: (e_loc, d, ff_v); wd: (e_loc, ff_v, d)
        j = lax.axis_index(mdl)
        n = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(n, d)
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        gates, expert_idx = _virtualize_routing(cfg, gates, expert_idx)

        fe = expert_idx.reshape(-1)
        ftok = jnp.repeat(jnp.arange(n), k_v)
        fgate = gates.reshape(-1)
        order = jnp.argsort(fe)
        se, stok, sgate = fe[order], ftok[order], fgate[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(n * k_v) - first
        capacity = int(-(-n * k_v // e_v) * cfg.capacity_factor) or 1
        lo = j * e_loc
        mine = (se >= lo) & (se < lo + e_loc) & (pos < capacity)
        dest = jnp.where(mine, (se - lo) * capacity + pos, e_loc * capacity)

        buf = jnp.zeros((e_loc * capacity + 1, d), xb.dtype).at[dest].set(xf[stok])
        expert_in = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
        gate_h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
        up_h = jnp.einsum("ecd,edf->ecf", expert_in, wu)
        expert_out = jnp.einsum("ecf,efd->ecd", gate_h * up_h, wd)

        flat = expert_out.reshape(e_loc * capacity, d)
        flat = jnp.concatenate([flat, jnp.zeros((1, d), xb.dtype)], axis=0)
        picked = flat[dest] * (sgate * mine).astype(xb.dtype)[:, None]
        partial = jnp.zeros((n, d), xb.dtype).at[stok].add(picked)
        out = lax.psum(partial, mdl)
        return out.reshape(xb.shape)

    return jax.shard_map(
        inner,
        mesh=ctx["mesh"],
        in_specs=(
            P(dp, None, None),
            P(),
            P(mdl, None, None),
            P(mdl, None, None),
            P(mdl, None, None),
        ),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])


def _moe_grouped(cfg, params: dict, xg: jax.Array) -> jax.Array:
    """Explicit-G dispatch: every op carries the (DP-sharded) group axis."""
    from repro.models import sharding as shd

    g, nl, d = xg.shape
    e, k, _, _ = _eff_dims(cfg)

    logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)  # (g, nl, k_real)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates, expert_idx = _virtualize_routing(cfg, gates, expert_idx)

    nk = nl * k
    fe = expert_idx.reshape(g, nk)
    ftok = jnp.broadcast_to(jnp.repeat(jnp.arange(nl), k)[None], (g, nk))
    fgate = gates.reshape(g, nk)
    order = jnp.argsort(fe, axis=-1)
    se = jnp.take_along_axis(fe, order, axis=-1)
    stok = jnp.take_along_axis(ftok, order, axis=-1)
    sgate = jnp.take_along_axis(fgate, order, axis=-1)

    first = jax.vmap(lambda s: jnp.searchsorted(s, s, side="left"))(se)
    pos = jnp.arange(nk)[None] - first
    capacity = int(-(-nk // e) * cfg.capacity_factor) or 1
    keep = pos < capacity
    dest = jnp.where(keep, se * capacity + pos, e * capacity)  # (g, nk)

    # dispatch (flattened group-offset scatter — stays group-local)
    goff = jnp.arange(g)[:, None] * (e * capacity + 1)
    dest_flat = (dest + goff).reshape(-1)
    src = jnp.take_along_axis(xg, stok[..., None], axis=1).reshape(-1, d)
    buf = jnp.zeros((g * (e * capacity + 1), d), xg.dtype).at[dest_flat].set(src)
    expert_in = buf.reshape(g, e * capacity + 1, d)[:, : e * capacity]
    expert_in = shd.constrain_moe_experts(expert_in.reshape(g, e, capacity, d))

    gate_h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"]))
    up_h = jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"])
    expert_out = jnp.einsum("gecf,efd->gecd", gate_h * up_h, params["w_down"])
    expert_out = shd.constrain_moe_experts(expert_out)

    # combine
    flat_out = expert_out.reshape(g, e * capacity, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    picked = jnp.take_along_axis(flat_out, dest[..., None], axis=1)
    picked = picked * (sgate * keep).astype(xg.dtype)[..., None]
    toff = jnp.arange(g)[:, None] * nl
    tok_flat = (stok + toff).reshape(-1)
    out = jnp.zeros((g * nl, d), xg.dtype).at[tok_flat].add(picked.reshape(-1, d))
    return shd.constrain_moe_tokens(out.reshape(g, nl, d))


def _moe_flat(cfg, params: dict, xf: jax.Array) -> jax.Array:
    """Token-choice dispatch on a flat (n, d) token block."""
    n, d = xf.shape
    e, k, _, _ = _eff_dims(cfg)

    # 1. route (router math in f32 for stability)
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)  # (n, k_real)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates, expert_idx = _virtualize_routing(cfg, gates, expert_idx)

    # 2. sort (token, choice) pairs by expert
    flat_expert = expert_idx.reshape(-1)  # (n*k,)
    flat_token = jnp.repeat(jnp.arange(n), k)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert)
    se, stok, sgate = flat_expert[order], flat_token[order], flat_gate[order]

    # 3. queue position within each expert
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(n * k) - first
    if n <= 512:
        # decode / tiny batches: dropless (worst-case one slot per token),
        # so serve_step matches the full forward exactly.
        capacity = n
    else:
        capacity = int(-(-n * k // e) * cfg.capacity_factor) or 1
    keep = pos < capacity
    dest = jnp.where(keep, se * capacity + pos, e * capacity)  # overflow row

    # 4. dispatch: (E*C + 1, d) buffer, sharded E over "model" upstream
    buf = jnp.zeros((e * capacity + 1, d), xf.dtype).at[dest].set(xf[stok])
    expert_in = buf[: e * capacity].reshape(e, capacity, d)

    # 5. expert FFN (SwiGLU), batched over E
    gate_h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"]))
    up_h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", gate_h * up_h, params["w_down"])

    # 6. combine
    flat_out = expert_out.reshape(e * capacity, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), xf.dtype)], axis=0)
    picked = flat_out[dest] * (sgate * keep).astype(xf.dtype)[:, None]
    return jnp.zeros((n, d), xf.dtype).at[stok].add(picked)


def aux_load_balance_loss(cfg, x: jax.Array, params: dict) -> jax.Array:
    """Switch-style load-balance auxiliary (fraction * probability)."""
    b, t, d = x.shape
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    prob = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac * prob)
