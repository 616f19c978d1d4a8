"""Sharded train/serve step construction.

The step runs entirely inside shard_map over the production mesh.  Gradient
synchronization is explicit and policy-dispatched:

  * FSDP-sharded params ('data' in spec): the AD transpose of the forward
    all-gather is a reduce-scatter over 'data' — gradients arrive already
    sharded and reduced (ZeRO-3).
  * model-replicated leaves: explicit psum over 'model' (their gradient
    contributions differ per TP rank).
  * data/pod-replicated leaves: explicit psum over 'data' / 'pod'.

All explicit psums flow through the collective dispatcher — this gradient
sync is exactly the traffic class the paper's policies tune.  The dispatcher
supports two sync modes (the §Perf hillclimb toggles them):

  bucketed=False — one psum per parameter leaf (NCCL-default-like)
  bucketed=True  — leaves are flattened into a single fused bucket per
                   (axis, reduction) class before the collective (fewer,
                   larger messages — the classic gradient-bucketing win)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..collectives.dispatch import dispatcher
from ..core.context import AxisKind
from ..models import loss_and_stats, loss_fn
from ..models.config import ModelConfig
from ..models.layers import MeshAxes
from ..models.moe import update_expert_bias
from .optimizer import AdamWConfig, adamw_init, adamw_update
from .schedule import cosine_schedule


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    opt: AdamWConfig = AdamWConfig()
    total_steps: int = 10_000
    warmup_steps: int = 100
    bucketed_grad_sync: bool = False


MOE_COUNTERS = ("pairs_here", "max_expert_pairs")


def _spec_axes(spec) -> set:
    out = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def _psum(x, axis: str, kind: int):
    return dispatcher().all_reduce(x, axis, axis_kind=kind)


def sync_grads(grads, specs, ax: MeshAxes, *, bucketed: bool = False):
    """Reduce gradients per the sharding rules above."""
    flat_g, tdef = jax.tree.flatten(grads)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda v: isinstance(v, P))
    assert len(flat_g) == len(flat_s), (len(flat_g), len(flat_s))

    plan = []  # (needs_model, needs_data) per leaf
    for s in flat_s:
        axes = _spec_axes(s)
        plan.append(("model" not in axes and ax.tp > 1,
                     "data" not in axes and ax.dp > 1))

    if not bucketed:
        out = []
        for g, (nm, nd) in zip(flat_g, plan):
            if nm:
                g = _psum(g, ax.model, AxisKind.MODEL)
            if nd:
                g = _psum(g, ax.data, AxisKind.DATA)
            if ax.pod:
                g = _psum(g, ax.pod, AxisKind.POD)
            out.append(g)
        flat_g = out
    else:
        # fuse same-class leaves into one flat bucket per collective
        for cls in [(True, False), (False, True), (True, True)]:
            idxs = [i for i, p in enumerate(plan) if p == cls]
            if not idxs:
                continue
            parts = [flat_g[i].reshape(-1).astype(jnp.float32)
                     for i in idxs]
            sizes = [p.size for p in parts]
            bucket = jnp.concatenate(parts)
            nm, nd = cls
            if nm:
                bucket = _psum(bucket, ax.model, AxisKind.MODEL)
            if nd:
                bucket = _psum(bucket, ax.data, AxisKind.DATA)
            off = 0
            for i, sz in zip(idxs, sizes):
                flat_g[i] = bucket[off:off + sz].reshape(
                    flat_g[i].shape).astype(flat_g[i].dtype)
                off += sz
        if ax.pod:
            parts = [g.reshape(-1).astype(jnp.float32) for g in flat_g]
            sizes = [p.size for p in parts]
            bucket = _psum(jnp.concatenate(parts), ax.pod, AxisKind.POD)
            off = 0
            for i, sz in enumerate(sizes):
                flat_g[i] = bucket[off:off + sz].reshape(
                    flat_g[i].shape).astype(flat_g[i].dtype)
                off += sz

    scale = 1.0 / (ax.dp * ax.n_pods)
    flat_g = [g * scale for g in flat_g]
    return jax.tree.unflatten(tdef, flat_g)


def batch_specs(cfg: ModelConfig, ax: MeshAxes, *, replicate_batch=False):
    dp_axes = None if replicate_batch else (
        (ax.pod, ax.data) if ax.pod else ax.data)
    s = {"tokens": P(dp_axes, None), "labels": P(dp_axes, None)}
    if cfg.family == "audio":
        s["frames"] = P(dp_axes, None, None)
    if cfg.family == "vlm":
        s["patches"] = P(dp_axes, None, None)
    return s


def opt_state_specs(param_specs):
    """PartitionSpec tree of the AdamW state (``adamw_init``'s layout)."""
    return {"m": param_specs, "v": param_specs, "step": P()}


def named_shardings(mesh: Mesh, specs):
    """The ``NamedSharding`` tree of a PartitionSpec tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda v: isinstance(v, P))


def make_train_step(cfg: ModelConfig, ax: MeshAxes, mesh: Mesh,
                    param_specs, step_cfg: TrainStepConfig
                    ) -> Tuple[Callable, Callable]:
    """Returns (jitted_step, opt_spec_tree).

    step(params, opt_state, batch) -> (params, opt_state, metrics)

    Where the loss comes with step stats (a dropless MoE stack), the step
    also updates each layer's selection bias from the step's expert loads
    (``moe.update_expert_bias``), and its metrics carry the stats'
    ``MOE_COUNTERS``.  Every metric is a replicated scalar.
    """
    bspecs = batch_specs(cfg, ax)
    opt_specs = opt_state_specs(param_specs)

    def objective(p, batch):
        # a stack without step stats goes through ``loss_fn`` by this
        # module's name, where the benchmark's fault tests replace it
        if not cfg.dropless:
            return loss_fn(p, batch, cfg, ax), {}
        return loss_and_stats(p, batch, cfg, ax)

    def local_step(params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(objective, has_aux=True)(
            params, batch)
        grads = sync_grads(grads, param_specs, ax,
                           bucketed=step_cfg.bucketed_grad_sync)
        lr_scale = cosine_schedule(opt_state["step"],
                                   step_cfg.total_steps,
                                   step_cfg.warmup_steps)
        new_params, opt_state, metrics = adamw_update(
            params, grads, opt_state, step_cfg.opt, lr_scale)
        # metrics reduced to replicated scalars
        loss = lax.psum(loss, ax.data) / ax.dp
        if ax.pod:
            loss = lax.psum(loss, ax.pod) / ax.n_pods
        metrics["loss"] = loss
        if stats:
            axes = (ax.data, ax.pod) if ax.pod else ax.data
            loads = jax.tree.map(lambda a: lax.psum(a, axes), stats["loads"])
            new_params = update_expert_bias(new_params, params, loads, cfg)
            metrics["pairs_here"] = lax.psum(stats["pairs_here"], axes)
            metrics["max_expert_pairs"] = lax.pmax(
                stats["max_expert_pairs"], axes)
        return new_params, opt_state, metrics

    sm = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_specs, opt_specs, bspecs),
        out_specs=(param_specs, opt_specs, P()),
        check_vma=False)

    jitted = jax.jit(
        sm,
        in_shardings=named_shardings(mesh, (param_specs, opt_specs, bspecs)),
        out_shardings=named_shardings(mesh, (param_specs, opt_specs, P())),
        donate_argnums=(0, 1))
    return jitted, opt_specs


def make_serve_step(cfg: ModelConfig, ax: MeshAxes, mesh: Mesh,
                    param_specs, cache_specs, *, mode: str,
                    replicate_batch: bool = False):
    """mode: 'prefill' (full forward, last-pos logits) or 'decode'
    (one token against the cache).  ``replicate_batch`` serves batch
    sizes smaller than the data axis (long_500k: B=1 replicated)."""
    from ..models import decode_step, prefill

    dp_axes = None if replicate_batch else (
        (ax.pod, ax.data) if ax.pod else ax.data)

    if mode == "prefill":
        bspecs = batch_specs(cfg, ax, replicate_batch=replicate_batch)
        bspecs.pop("labels", None)     # prefill consumes tokens only
        out_spec = P(dp_axes, None, None)

        def local_prefill(params, batch):
            return prefill(params, batch, cfg, ax)

        sm = jax.shard_map(local_prefill, mesh=mesh,
                           in_specs=(param_specs, bspecs),
                           out_specs=out_spec, check_vma=False)
        return jax.jit(sm)

    tok_spec = P(dp_axes, None)

    def local_decode(params, token, caches, pos):
        return decode_step(params, token, caches, pos, cfg, ax)

    sm = jax.shard_map(
        local_decode, mesh=mesh,
        in_specs=(param_specs, tok_spec, cache_specs, P(dp_axes)),
        out_specs=(tok_spec, cache_specs), check_vma=False)
    return jax.jit(sm, donate_argnums=(2,))
