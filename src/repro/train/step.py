"""Train-step builder: DynaFlow forward -> loss -> grads -> AdamW.

The step function is pure and shard_map-friendly: all collectives go
through ``repro.dist.collectives`` (no-ops without a mesh, real
collectives inside shard_map).  Gradient reduction rules:

  * grads are partial over the data axes (different samples) -> psum over
    ('pod','data') — optionally int8-compressed with error feedback;
  * under sequence-parallel training, grads of params *replicated* over
    'model' (norm gains, routers, shared experts) are partial over the
    sequence shards -> additional psum over 'model';
  * params sharded over 'data' (FSDP WeightGather) skip the data psum:
    the all-gather's AD transpose already reduce-scatters them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .._deprecation import warn_once
from ..core.plan_store import checkpoint_plan_store, resolve_plan_store
from ..core.scheduler import ScheduleContext
from ..dist import collectives as col
from ..models.base import build_forward
from ..optim import AdamWConfig, adamw_init, adamw_update
from ..optim.schedules import cosine_schedule


@dataclasses.dataclass
class TrainStepConfig:
    optimizer: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    remat: bool = True
    remat_policy: str = "full"     # full | dots
    grad_accum: int = 1
    compress_grads: bool = False     # int8 DP all-reduce + error feedback
    warmup: int = 100
    total_steps: int = 10000
    lowered: bool = True             # slot-based lowered plan replay


def _flat_axes(pspec) -> set:
    out = set()
    for entry in pspec:
        if isinstance(entry, str):
            out.add(entry)
        elif entry:
            out.update(entry)
    return out


def reduce_grads(grads, pspecs, mesh_info, sp_train: bool,
                 compress: bool = False, errors=None):
    """Apply the reduction rules above.  Returns (grads, new_errors)."""
    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_s = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_g) == len(flat_s), (len(flat_g), len(flat_s))
    flat_e = (jax.tree_util.tree_leaves(errors) if errors is not None
              else [None] * len(flat_g))
    outs, new_errs = [], []
    for g, spec, err in zip(flat_g, flat_s, flat_e):
        axes = _flat_axes(spec)
        red = g
        new_err = err
        for ax in mesh_info.dp_axes:
            if ax in axes:
                continue  # FSDP leaf: already reduce-scattered on this axis
            if compress and ax == "data":
                red, new_err = col.compressed_psum(red, ax, err)
            else:
                red = col.psum(red, ax)
        if sp_train and "model" not in axes:
            red = col.psum(red, "model")
        outs.append(red)
        new_errs.append(new_err if new_err is not None
                        else jnp.zeros_like(g))
    return (jax.tree_util.tree_unflatten(tdef, outs),
            jax.tree_util.tree_unflatten(tdef, new_errs))


def global_grad_norm(grads, pspecs, mesh_info):
    """Global ||g||² under SPMD: per-leaf local sum-of-squares, psum'd over
    the axes the leaf is *sharded* on (replicated leaves count once) —
    every chip gets the identical norm, so clipping stays consistent."""
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_s = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, tuple))
    by_axes: dict = {}
    for g, spec in zip(flat_g, flat_s):
        # sum sq over exactly the mesh axes this (post-reduction) grad leaf
        # is sharded on; replicated leaves count once
        axes = tuple(sorted(_flat_axes(spec) & {"data", "model"}))
        by_axes[axes] = by_axes.get(axes, 0.0) + jnp.sum(
            g.astype(jnp.float32) ** 2)
    total = 0.0
    for axes, sq in by_axes.items():
        for ax in axes:
            sq = col.psum(sq, ax)
        total = total + sq
    return jnp.sqrt(total)


def build_train_step(model, scheduler, B_loc: int, S: int,
                     cfg: TrainStepConfig,
                     info: Optional[ScheduleContext] = None,
                     plan_store=None, plan_store_path: Optional[str] = None):
    """Deprecated pre-facade entry point — build the Program instead:
    ``repro.api.compile(model, policy=...).train_step(...)``."""
    warn_once("repro.train.build_train_step",
              "repro.api.compile(...).train_step(...)")
    return _build_train_step(model, scheduler, B_loc, S, cfg, info,
                             plan_store=plan_store,
                             plan_store_path=plan_store_path)


def _build_train_step(model, scheduler, B_loc: int, S: int,
                      cfg: TrainStepConfig,
                      info: Optional[ScheduleContext] = None,
                      plan_store=None,
                      plan_store_path: Optional[str] = None,
                      verify: str = "off",
                      verify_sink: Optional[list] = None):
    """Returns (train_step, segments, binputs, init_opt).

    ``scheduler`` may be an ``OpSchedulerBase`` or a ``StrategyPolicy``
    (``build_forward`` resolves policies per segment context).

    ``train_step(params, opt_state, batch, step) ->
        (params, opt_state, metrics)``.

    ``plan_store``: optional shared ``PlanStore`` so rebuilding the step
    (new seq-len bucket, restart after preemption) specializes the
    already-lowered segment plans instead of re-running analysis+lowering.
    ``plan_store_path``: persist that store on disk — a relaunched
    trainer restores the canonical lowerings and rebuilds its step
    without a single ``lower`` call (the store is checkpointed right
    after the forward is built).
    """
    plan_store = resolve_plan_store(plan_store, plan_store_path)
    segs, binputs = model.build_segments("train", B_loc, S)
    info = info or ScheduleContext(
        local_batch=B_loc, global_batch=B_loc, seq_len=S, phase="train",
        arch=model.cfg.name)
    fwd = build_forward(segs, scheduler, info, remat=cfg.remat,
                        remat_policy=cfg.remat_policy, lowered=cfg.lowered,
                        plan_cache=plan_store,
                        op_config=model.op_closure_config(),
                        verify=verify, verify_sink=verify_sink)
    checkpoint_plan_store(plan_store)
    pspecs = model.param_pspecs(segs)
    sp_train = model.uses_sp("train")
    mesh_info = model.mesh

    def loss_fn(params, batch):
        out = fwd(params, batch)
        local_sum = jnp.sum(out["loss_sum"])
        local_cnt = jnp.sum(out["token_count"])
        total_cnt = local_cnt
        for ax in mesh_info.dp_axes:
            total_cnt = col.psum(total_cnt, ax)
        total_cnt = jax.lax.stop_gradient(jnp.maximum(total_cnt, 1.0))
        return local_sum / total_cnt, (local_sum, local_cnt)

    def one_batch_grads(params, batch):
        (_, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, aux

    def train_step(params, opt_state, batch, step):
        if cfg.grad_accum > 1:
            # micro-batch scan over a leading accum dim of the batch
            def body(acc, mb):
                g, aux = one_batch_grads(params, mb)
                return (jax.tree_util.tree_map(jnp.add, acc[0], g),
                        (acc[1][0] + aux[0], acc[1][1] + aux[1])), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, aux), _ = jax.lax.scan(
                body, (zeros, (jnp.zeros(()), jnp.zeros(()))), batch)
        else:
            grads, aux = one_batch_grads(params, batch)
        errors = opt_state.get("grad_errors") if cfg.compress_grads else None
        grads, new_errors = reduce_grads(
            grads, pspecs, mesh_info, sp_train,
            compress=cfg.compress_grads, errors=errors)
        lr = cosine_schedule(step, cfg.warmup, cfg.total_steps,
                             cfg.optimizer.lr)
        gnorm = global_grad_norm(grads, pspecs, mesh_info)
        new_params, new_opt, gnorm = adamw_update(
            params, grads, opt_state, cfg.optimizer, lr=lr, gnorm=gnorm)
        if cfg.compress_grads:
            new_opt["grad_errors"] = new_errors
        loss_sum, cnt = aux
        for ax in mesh_info.dp_axes:
            loss_sum = col.psum(loss_sum, ax)
            cnt = col.psum(cnt, ax)
        metrics = {"loss": loss_sum / jnp.maximum(cnt, 1.0),
                   "grad_norm": gnorm, "lr": lr,
                   "tokens": cnt}
        return new_params, new_opt, metrics

    def init_opt(params):
        opt = adamw_init(params, cfg.optimizer)
        if cfg.compress_grads:
            opt["grad_errors"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return opt

    return train_step, segs, binputs, init_opt
