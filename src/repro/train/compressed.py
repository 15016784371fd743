"""Compressed SwitchAgg training step — the paper's full dataplane.

``build_train_step`` (step.py) realizes the aggregation *tree* as a
collective schedule (flat/tree/gather).  This module adds the third mode,
``tree_compress``: per-worker gradients become top-k KV payloads, cross the
scarce (inter-pod) links as (key, value) streams, and are combined by the
bounded-memory FPE/BPE node — the paper's aggregation packet flow, with
error feedback making the compression unbiased over steps.

The whole step runs inside ``jax.shard_map`` manual over the dp axes
(per-worker gradients exist only there); the model/TP axis stays automatic.
MoE expert-parallel dispatch uses the local (non-a2a) path inside the
manual region — EP's all-to-all is a permutation, not a reduction, and is
orthogonal to the gradient exchange under study (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as coll
from repro.core.collectives import GradAggMode
from repro.models.attention import ShardingPolicy
from repro.models.model import LMModel
from repro.models.transformer import ApplyOptions
from repro.obs import metrics as obs_metrics
from repro.optim import AdamWConfig, adamw_update
from repro.train.step import TrainProfile, make_param_specs, make_opt_specs

from repro.models import sharding as shd


def init_exchange_residuals(params_example, mesh, prof: TrainProfile):
    """Error-feedback state: one flat per-dp-shard residual per param leaf.

    Returns (residuals pytree of global arrays, their PartitionSpecs).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    leaf_axis = prof.dp_axes[0]
    world = 1
    for a in prof.dp_axes:
        world *= sizes[a]
    leaf_size = sizes[leaf_axis]

    def one(p):
        n = 1
        for d in p.shape:
            n *= d
        padded = n + ((-n) % leaf_size)
        return jnp.zeros((world * (padded // leaf_size),), jnp.float32)

    res = jax.tree.map(one, params_example)
    spec = jax.tree.map(lambda _: P(prof.dp_axes), params_example)
    return res, spec


def build_compressed_train_step(
    cfg: ModelConfig,
    mesh,
    prof: TrainProfile,
    opt_cfg: AdamWConfig,
    lr_fn,
    *,
    batch_example: Any,
    params_example: Any,
    k_fraction: float = 0.01,
    fpe_capacity: int = 0,
    mode: GradAggMode | None = None,
    wire_dtype=None,
    plan=None,
):
    """Returns (jitted step, shardings).  Step signature:
    (params, opt_state, residuals, batch, step) ->
    (params, opt_state, residuals, metrics).

    ``mode=TREE`` gives the *post-accumulation* exact exchange: microbatch
    gradients accumulate LOCALLY inside the manual region (zero collectives
    in the loop — unlike the pjit path, where the loop-carried sharded sum
    forces a reduction per microbatch), then ONE tree exchange crosses the
    wire.  ``wire_dtype`` (e.g. bf16) casts just the exchanged bytes.

    ``plan`` (a planner ``ExchangePlan``) overrides mode / k_fraction /
    fpe_capacity with the controller's decision for this job (DESIGN.md §3);
    its level ordering must use the profile's dp axes.  Compressed plans run
    the multi-level cascade dataplane across the upper hops, the plan's
    combiner budget partitioned per level (DESIGN.md §6)."""
    cascade = None
    if plan is not None:
        mode = plan.mode
        k_fraction = plan.k_fraction
        fpe_capacity = plan.fpe_capacity
        plan_axes = (plan.leaf_axis, *plan.upper_axes)
        assert set(plan_axes) == set(prof.dp_axes), (
            f"plan axes {plan_axes} != profile dp axes {prof.dp_axes}")
        prof = dataclasses.replace(prof, dp_axes=plan_axes)
        cascade = coll.cascade_for_plan(plan)
    # model math sees a single logical worker (dp manual, tp via GSPMD auto)
    model = LMModel(
        cfg,
        policy=ShardingPolicy(),  # no in-graph constraints inside the region
        opt=ApplyOptions(q_chunk=prof.q_chunk, k_chunk=prof.k_chunk,
                         moe_token_chunk=prof.moe_token_chunk, remat=prof.remat),
    )
    pspecs = make_param_specs(params_example, cfg, mesh, prof)
    ospecs = make_opt_specs(params_example, pspecs, mesh, prof, opt_cfg)
    bspecs = shd.batch_specs(batch_example, prof.dp_axes)
    res_example, res_specs = init_exchange_residuals(params_example, mesh, prof)
    s = functools.partial(NamedSharding, mesh)

    leaf_axis = prof.dp_axes[0]
    upper_axes = tuple(prof.dp_axes[1:])
    # NOTE: leaf = first dp axis. With dp_axes=('pod','data') the scarce pod
    # axis would be the LEAF; callers order dp_axes cheap-first for the tree
    # ('data' before 'pod') — asserted here.
    if "pod" in prof.dp_axes:
        assert prof.dp_axes[0] != "pod", (
            "compressed exchange wants dp_axes ordered (data, pod): "
            "reduce the cheap axis first, compress across the scarce one")

    # shard_map specs may only mention MANUAL axes; the auto (model/TP) axis
    # sharding flows through implicitly.  Keep only dp-axis references.
    manual = set(prof.dp_axes)

    def _manual_only(spec: P) -> P:
        def keep(e):
            if e is None:
                return None
            if isinstance(e, (tuple, list)):
                kept = tuple(a for a in e if a in manual)
                return kept if kept else None
            return e if e in manual else None

        return P(*(keep(e) for e in spec))

    pspecs_region = jax.tree.map(_manual_only, pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
    bspecs_region = jax.tree.map(
        lambda _: P(prof.dp_axes, *([None] * 0)), batch_example)

    xmode = mode or GradAggMode.TREE_COMPRESS

    def region(params, batch, residuals, step_idx):
        def loss_of(p, b):
            loss, aux = model.loss_fn(p, b)
            return loss, aux

        n = max(prof.accum_steps, 1)
        if n > 1:
            micro = jax.tree.map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch)

            def mb(carry, b):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, b)
                # LOCAL accumulation: dp axes are manual here, so no
                # per-microbatch collective is emitted.
                gsum = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), _ = jax.lax.scan(mb, (g0, 0.0), micro)
            loss = lsum / n
            grads = jax.tree.map(lambda g: g / n, grads)
        else:
            (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        # mean over workers
        w = 1.0
        for ax in prof.dp_axes:
            w *= jax.lax.axis_size(ax)
        grads = jax.tree.map(lambda g: g / w, grads)
        if wire_dtype is not None:
            grads = jax.tree.map(lambda g: g.astype(wire_dtype), grads)
        new_grads, new_res = coll.exchange_in_shardmap(
            grads, xmode, leaf_axis, upper_axes,
            k_fraction=k_fraction, fpe_capacity=fpe_capacity,
            residuals=residuals, cascade=cascade,
        )
        if wire_dtype is not None:
            new_grads = jax.tree.map(lambda g: g.astype(jnp.float32), new_grads)
        loss = jax.lax.pmean(loss, prof.dp_axes)
        return new_grads, new_res, loss

    def batch_region_specs(b):
        def one(leaf):
            nd = len(leaf.shape)
            return P(prof.dp_axes, *([None] * (nd - 1)))

        return jax.tree.map(one, b)

    mapped = jax.shard_map(
        region,
        mesh=mesh,
        in_specs=(pspecs_region, batch_region_specs(batch_example),
                  jax.tree.map(lambda _: P(prof.dp_axes), res_example), P()),
        out_specs=(pspecs_region,
                   jax.tree.map(lambda _: P(prof.dp_axes), res_example), P()),
        axis_names=set(prof.dp_axes),
        check_vma=False,
    )

    def train_step(params, opt_state, residuals, batch, step_idx):
        grads, new_res, loss = mapped(params, batch, residuals, step_idx)
        lr = lr_fn(step_idx)
        new_params, new_opt, stats = adamw_update(grads, opt_state, params,
                                                  opt_cfg, lr)
        new_params = jax.tree.map(
            lambda p, sp: jax.lax.with_sharding_constraint(p, s(sp)),
            new_params, pspecs)
        return new_params, new_opt, new_res, {"loss": loss, **stats}

    shardings = {
        "params": jax.tree.map(s, pspecs),
        "opt": jax.tree.map(s, ospecs, is_leaf=lambda x: isinstance(x, P)),
        "batch": jax.tree.map(s, bspecs),
        "residuals": jax.tree.map(s, res_specs, is_leaf=lambda x: isinstance(x, P)),
        "pspecs": pspecs,
        "res_example": res_example,
    }
    step_fn = jax.jit(
        train_step,
        in_shardings=(shardings["params"], shardings["opt"],
                      shardings["residuals"], shardings["batch"], None),
        out_shardings=(shardings["params"], shardings["opt"],
                       shardings["residuals"], None),
        donate_argnums=(0, 1, 2),
    )
    # build-time exchange gauges + per-call span/latency series
    # (DESIGN.md §11); the wrapper forwards .lower()/.trace() so dryrun's
    # AOT path is untouched
    reg = obs_metrics.get_registry()
    lbl = {"mode": mode.value if hasattr(mode, "value") else str(mode)}
    reg.gauge("train.exchange.k_fraction", **lbl).set(k_fraction)
    reg.gauge("train.exchange.fpe_capacity", **lbl).set(fpe_capacity)
    if plan is not None:
        reg.gauge("train.exchange.scarce_link_bytes",
                  **lbl).set(plan.scarce_link_bytes)
    step_fn = obs_metrics.instrument_step(step_fn, name="train.step",
                                          labels=lbl)
    return step_fn, shardings
