"""Train-step factories: the pjit (DP x TP) path and the FD-compressed
pure-DP shard_map path.

The pjit path is what the multi-pod dry-run lowers; the compressed path is
the paper's protocol working as gradient compression (see
optim/grad_compress.py) — selectable via ``TrainConfig.grad_compression``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.sharding import batch_sharding, data_axes, param_shardings
from repro.optim.adamw import AdamWState, adamw_init, adamw_state_shardings, adamw_update
from repro.optim.grad_compress import (
    FDCompressConfig,
    compress_and_aggregate,
    init_residuals,
)
from repro.optim.schedule import warmup_cosine


class TrainConfig(NamedTuple):
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    grad_compression: FDCompressConfig | None = None


class TrainState(NamedTuple):
    params: dict
    opt: AdamWState
    residuals: dict | None = None  # error feedback (compressed path only)


def init_train_state(lm, key, tcfg: TrainConfig) -> TrainState:
    params = lm.init(key)
    opt = adamw_init(params)
    res = init_residuals(params) if tcfg.grad_compression else None
    return TrainState(params=params, opt=opt, residuals=res)


def train_state_shardings(state_template: TrainState, mesh: Mesh) -> TrainState:
    ps = param_shardings(state_template.params, mesh)
    os_ = adamw_state_shardings(state_template.params, ps, mesh)
    res = (
        jax.tree.map(lambda _: NamedSharding(mesh, P()), state_template.residuals)
        if state_template.residuals is not None
        else None
    )
    return TrainState(params=ps, opt=os_, residuals=res)


def _lr(tcfg: TrainConfig, count):
    # count is the pre-increment step; +1 so the very first update is not lr=0
    return warmup_cosine(
        count + 1, peak_lr=tcfg.peak_lr, warmup_steps=tcfg.warmup_steps, total_steps=tcfg.total_steps
    )


def make_train_step(lm, tcfg: TrainConfig):
    """The pjit path: global-batch loss, XLA-inserted DP psums, TP via the
    param shardings.  jit it with in/out shardings from train_state_shardings
    + batch_sharding."""

    def train_step(state: TrainState, batch: dict):
        loss, grads = jax.value_and_grad(lm.loss)(state.params, batch)
        new_params, new_opt = adamw_update(
            grads,
            state.opt,
            state.params,
            lr=_lr(tcfg, state.opt.count),
            b1=tcfg.b1,
            b2=tcfg.b2,
            weight_decay=tcfg.weight_decay,
            grad_clip=tcfg.grad_clip,
        )
        metrics = {"loss": loss, "step": new_opt.count}
        return TrainState(new_params, new_opt, state.residuals), metrics

    return train_step


def make_jitted_train_step(lm, tcfg: TrainConfig, mesh: Mesh, state_template: TrainState, batch_shape):
    """jit + shardings wired up; returns (step_fn, state_shardings)."""
    st_sh = train_state_shardings(state_template, mesh)
    b_sh = {"tokens": batch_sharding(mesh, batch_shape[0])}
    step = jax.jit(
        make_train_step(lm, tcfg),
        in_shardings=(st_sh, b_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )
    return step, st_sh


def make_compressed_train_step(
    lm,
    tcfg: TrainConfig,
    mesh: Mesh,
    axes: tuple | None = None,
    compress_axis: str | None = None,
):
    """Pure-DP shard_map path with FD gradient compression + error feedback.

    Params/opt are replicated across DP (the compression replaces the dense
    gradient all-reduce); batch is sharded over the DP axes (pass
    ``axes=mesh.axis_names`` to use every axis as DP).

    ``compress_axis``: hierarchical mode — gradients are densely pmean'd over
    the *other* (fast-ICI) axes and FD-compressed only across
    ``compress_axis`` (the slow inter-pod/DCN link).  This is the paper's own
    topology: pods = sites, the cross-pod link = the coordinator channel.
    """

    ccfg = tcfg.grad_compression or FDCompressConfig()
    dp = tuple(axes) if axes is not None else data_axes(mesh)
    axis = dp[-1] if len(dp) == 1 else dp  # compression runs over these axes
    intra: tuple = ()
    if compress_axis is not None:
        intra = tuple(a for a in dp if a != compress_axis)
        axis = compress_axis

    def inner(state: TrainState, batch: dict):
        loss, grads = jax.value_and_grad(lm.loss)(state.params, batch)
        loss = jax.lax.pmean(loss, dp if len(dp) > 1 else dp[-1])
        if intra:  # dense reduce on the fast link first
            grads = jax.lax.pmean(grads, intra if len(intra) > 1 else intra[-1])
        grads, new_res, stats = compress_and_aggregate(
            grads, state.residuals, ccfg._replace(axis=axis)
        )
        new_params, new_opt = adamw_update(
            grads,
            state.opt,
            state.params,
            lr=_lr(tcfg, state.opt.count),
            b1=tcfg.b1,
            b2=tcfg.b2,
            weight_decay=tcfg.weight_decay,
            grad_clip=tcfg.grad_clip,
        )
        metrics = {
            "loss": loss,
            "step": new_opt.count,
            "comm_full_bytes": stats.full_bytes,
            "comm_compressed_bytes": stats.compressed_bytes,
        }
        return TrainState(new_params, new_opt, new_res), metrics

    # Spec prefixes: state/metrics replicated, batch sharded over DP.
    step = jax.jit(
        jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), {"tokens": P(dp, None)}),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    return step
