"""Gradient/KV aggregation collectives — the SwitchAgg dataplane on a mesh.

Three exchange modes (the paper's comparison axis):

  * ``flat``          — one all-reduce over every reduction axis at once.
                        This is the no-in-network-aggregation baseline: the
                        scarce inter-pod links carry full gradient bytes.
  * ``tree``          — SwitchAgg schedule: reduce-scatter over the cheap
                        intra-pod axis first, all-reduce only the 1/fanin
                        shard over the scarce pod axis, all-gather back.
                        Inter-pod traffic drops by the intra-pod fanin —
                        in-network aggregation realized as a collective
                        schedule (DESIGN.md §2 insight (a)).
  * ``tree_compress`` — additionally top-k compress the shard before it
                        crosses the pod axis; the KV streams are combined by
                        the bounded-memory FPE/BPE aggregator (insight (b)).

All functions here are *manual-collective* code meant to run inside
``jax.shard_map`` over the reduction axes (model axis stays auto/SPMD).
Use :func:`make_grad_exchange` to get a jit-ready pytree-level exchanger.
"""

from __future__ import annotations

import enum
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import aggops
from . import compressor as comp
from . import dataplane
from . import kvagg


class GradAggMode(str, enum.Enum):
    GATHER = "gather"  # parameter-server: raw flows to the reducer (paper's no-agg baseline)
    FLAT = "flat"  # one flat all-reduce over every chip (single-switch / DAIET-like)
    TREE = "tree"  # SwitchAgg: hierarchical on-path reduction
    TREE_COMPRESS = "tree_compress"  # + bounded-memory KV compression on the scarce link


# ---------------------------------------------------------------------------
# Single-array exchanges (inside shard_map; axes are bound axis names).
# ---------------------------------------------------------------------------


def flat_allreduce(x: jnp.ndarray, axes: tuple[str, ...]) -> jnp.ndarray:
    """Baseline: one flat psum over all reduction axes."""
    return jax.lax.psum(x, axes)


def tree_allreduce(x: jnp.ndarray, leaf_axis: str, upper_axes: tuple[str, ...]) -> jnp.ndarray:
    """SwitchAgg tree schedule on a 1-D-reshapeable array.

    reduce-scatter(leaf) -> psum(upper, on the shard) -> all-gather(leaf).
    Equivalent to flat psum (tested) but the upper (scarce) axes carry only
    ``1/fanin(leaf)`` of the bytes.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    fanin = jax.lax.axis_size(leaf_axis)
    pad = (-n) % fanin
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = jax.lax.psum_scatter(flat, leaf_axis, scatter_dimension=0, tiled=True)
    if upper_axes:
        shard = jax.lax.psum(shard, upper_axes)
    full = jax.lax.all_gather(shard, leaf_axis, axis=0, tiled=True)
    if pad:
        full = full[:n]
    return full.reshape(x.shape)


class CompressedExchangeState(NamedTuple):
    residual: jnp.ndarray  # error-feedback memory for the local shard [flat]


def tree_compress_allreduce(
    x: jnp.ndarray,
    residual: jnp.ndarray,
    leaf_axis: str,
    upper_axes: tuple[str, ...],
    *,
    k: int,
    fpe_capacity: int = 0,
    cascade: dataplane.CascadePlan | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compressed SwitchAgg exchange for one flat-reshapeable array.

    1. exact reduce-scatter over the cheap leaf axis (intra-pod);
    2. top-k compress the local shard (+ error feedback residual);
    3. the KV stream crosses the scarce upper axes as a multi-level
       CASCADE (``core.dataplane``): hop *i* all-gathers over upper axis
       *i* and pushes the merged stream through that level's bounded-memory
       FPE/BPE node, whose eviction-plus-flush stream feeds hop *i+1*;
    4. decompress to the dense shard; all-gather over the leaf axis.

    ``cascade`` carries the planner's per-level node specs (capacity per
    hop — DESIGN.md §6); when None, every hop gets ``fpe_capacity`` (0 =
    the exact unbounded node).  Gradient exchange is a SUM dataplane:
    non-sum cascades are rejected because decompression scatter-adds.

    Returns (result, new_residual).  Result is *approximate* (top-k), with
    error feedback making the bias vanish across steps.
    """
    if cascade is None:
        cascade = dataplane.CascadePlan(
            op="sum",
            levels=dataplane.uniform_levels(fpe_capacity,
                                            len(upper_axes)))
    if cascade.op != "sum":
        raise ValueError(f"gradient exchange needs a sum cascade, got {cascade.op!r}")
    if upper_axes and len(cascade.levels) != len(upper_axes):
        raise ValueError(
            f"cascade has {len(cascade.levels)} level(s) for "
            f"{len(upper_axes)} upper axis hop(s)")
    flat = x.reshape(-1)
    n = flat.shape[0]
    fanin = jax.lax.axis_size(leaf_axis)
    pad = (-n) % fanin
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = jax.lax.psum_scatter(flat, leaf_axis, scatter_dimension=0, tiled=True)
    shard_n = shard.shape[0]

    acc = shard + residual
    kk = min(k, shard_n)
    _, idx = jax.lax.top_k(jnp.abs(acc), kk)
    vals = acc[idx]
    new_residual = acc.at[idx].set(0.0)

    if upper_axes:
        keys = idx.astype(jnp.int32)
        # The scarce links carry only the KV stream, one cascade level per hop.
        for ax, spec in zip(upper_axes, cascade.levels):
            gk = jax.lax.all_gather(keys, ax, axis=0, tiled=True)
            gv = jax.lax.all_gather(vals, ax, axis=0, tiled=True)
            keys, vals, _ = dataplane.run_level(gk, gv, spec, cascade.op)
        dense = comp.decompress_sum(keys, vals, size=shard_n)
    else:
        dense = comp.decompress_sum(idx.astype(jnp.int32), vals, size=shard_n)

    full = jax.lax.all_gather(dense, leaf_axis, axis=0, tiled=True)
    if pad:
        full = full[:n]
    return full.reshape(x.shape), new_residual


# ---------------------------------------------------------------------------
# Pytree-level exchange builders (shard_map wrappers).
# ---------------------------------------------------------------------------


def exchange_in_shardmap(
    grads,
    mode: GradAggMode,
    leaf_axis: str,
    upper_axes: tuple[str, ...],
    *,
    k_fraction: float = 0.01,
    fpe_capacity: int = 0,
    residuals=None,
    cascade: dataplane.CascadePlan | None = None,
):
    """Apply the chosen exchange to every leaf of a gradient pytree.

    Must be called from inside a shard_map whose manual axes include
    ``leaf_axis`` and ``upper_axes``.  Returns (new_grads, new_residuals).
    """
    all_axes = (leaf_axis, *upper_axes)
    if mode == GradAggMode.FLAT:
        return jax.tree.map(lambda g: flat_allreduce(g, all_axes), grads), residuals
    if mode == GradAggMode.TREE:
        return (
            jax.tree.map(lambda g: tree_allreduce(g, leaf_axis, upper_axes), grads),
            residuals,
        )
    if mode == GradAggMode.TREE_COMPRESS:
        if residuals is None:
            raise ValueError("TREE_COMPRESS needs residual state")
        outs = []
        leaves, treedef = jax.tree.flatten(grads)
        res_leaves = treedef.flatten_up_to(residuals)
        new_res = []
        for g, r in zip(leaves, res_leaves):
            k = max(1, int(g.size / jax.lax.axis_size(leaf_axis) * k_fraction))
            o, nr = tree_compress_allreduce(
                g, r, leaf_axis, upper_axes, k=k, fpe_capacity=fpe_capacity,
                cascade=cascade,
            )
            outs.append(o)
            new_res.append(nr)
        return treedef.unflatten(outs), treedef.unflatten(new_res)
    raise ValueError(mode)


def init_residuals(grads_shape_tree, leaf_axis_size: int, world_size: int = 1):
    """Residual (error-feedback) state per gradient leaf.

    Each device holds the residual of its scattered shard:
    ``shard_n = ceil(param_size / leaf_fanin)``.  The *global* array is
    ``world_size * shard_n`` long and enters the shard_map with spec
    ``P((pod, data))`` so every device sees exactly its own shard's state.
    """

    def one(leaf):
        import numpy as np

        n = int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else int(leaf)
        padded = n + ((-n) % leaf_axis_size)
        return jnp.zeros((world_size * (padded // leaf_axis_size),), jnp.float32)

    return jax.tree.map(one, grads_shape_tree)


def cascade_for_plan(plan) -> dataplane.CascadePlan | None:
    """The one plan->cascade policy for the compressed gradient exchange:
    TREE_COMPRESS plans with upper hops run the per-hop cascade (budget
    split per level), everything else runs cascade-free.  plan.op flows
    through so a non-sum plan trips the sum-only guard in
    :func:`tree_compress_allreduce` instead of silently running as SUM.
    Used by both :func:`exchange_from_plan` and
    ``train.compressed.build_compressed_train_step``.
    """
    if plan.mode == GradAggMode.TREE_COMPRESS and plan.upper_axes:
        return dataplane.cascade_from_exchange_plan(plan)
    return None


def exchange_from_plan(grads, plan, *, residuals=None):
    """Run the exchange a planner ``ExchangePlan`` describes.

    Mode, level ordering, top-k fraction, and FPE capacity all come from the
    plan (the controller's decision for this job under current tenancy) —
    callers stop hardcoding them.  The compressed mode executes the plan as
    a multi-level CASCADE: the plan's combiner budget is partitioned across
    its upper-axis hops (``dataplane.cascade_from_exchange_plan``) so every
    hop runs a bounded node at its own memory slice — DESIGN.md §6.
    Must be called inside a shard_map whose manual axes include the plan's
    axes.  ``plan`` is duck-typed to avoid a circular import with
    ``planner``.
    """
    cascade = cascade_for_plan(plan)
    return exchange_in_shardmap(
        grads, plan.mode, plan.leaf_axis, tuple(plan.upper_axes),
        k_fraction=plan.k_fraction, fpe_capacity=plan.fpe_capacity,
        residuals=residuals, cascade=cascade,
    )


# ---------------------------------------------------------------------------
# KV-stream tree aggregation — the word-count / MapReduce dataplane.
# ---------------------------------------------------------------------------


class KVTreeResult(NamedTuple):
    keys: jnp.ndarray
    values: jnp.ndarray
    level_in: jnp.ndarray  # [n_levels] pairs entering each level's node
    level_out: jnp.ndarray  # [n_levels] pairs leaving each level's node
    level_evict: jnp.ndarray  # [n_levels] FPE evictions at each level's node


def kv_tree_aggregate(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    level_axes: tuple[str, ...],
    *,
    fpe_capacity: int,
    ways: int = 4,
    bpe: bool = True,
    op: str = "sum",
    plan: dataplane.CascadePlan | None = None,
) -> KVTreeResult:
    """Aggregate per-worker KV streams up an aggregation tree.

    At each level the streams of that level's group are merged (Theorem 2.1:
    all-gather over the level axis == the node receiving all child flows) and
    pushed through that level's bounded-memory SwitchAgg node
    (``dataplane.run_level``).  Output stream feeds the next level.
    Per-level in/out/eviction counts give the measured reduction ratio of
    every hop (paper Fig. 2b / Fig. 9).

    ``op`` is any registered AggOp (DESIGN.md §6): carried values enter the
    tree via ``prepare`` and the root stream is ``finalize``d, so e.g.
    ``mean`` stays exact across levels.  ``plan`` overrides the uniform
    (fpe_capacity, ways, bpe) node geometry with the controller's per-level
    memory partition.

    Runs inside shard_map over ``level_axes``.
    """
    if plan is None:
        plan = dataplane.CascadePlan(
            op=op,
            levels=dataplane.uniform_levels(fpe_capacity, len(level_axes),
                                            ways=ways, bpe=bpe))
    elif op not in ("sum", plan.op):
        # plan.op drives the cascade; a conflicting explicit op is a caller
        # bug ("sum" is indistinguishable from the default and defers)
        raise ValueError(f"op={op!r} conflicts with plan.op={plan.op!r}")
    if len(plan.levels) != len(level_axes):
        raise ValueError(f"plan has {len(plan.levels)} level(s) for "
                         f"{len(level_axes)} tree axes")
    aggop = aggops.get(plan.op)
    lvl_in, lvl_out, lvl_ev = [], [], []
    k, v = keys, aggop.prepare_values(values)
    for ax, spec in zip(level_axes, plan.levels):
        gk = jax.lax.all_gather(k, ax, axis=0, tiled=True)
        gv = jax.lax.all_gather(v, ax, axis=0, tiled=True)
        # Compact the stream: keep a fixed-size output per level to bound
        # downstream shapes (real switches flush variable traffic; fixed
        # shapes are the TPU adaptation — sized at capacity + input).
        k, v, stats = dataplane.run_level(gk, gv, spec, plan.op)
        lvl_in.append(stats.n_in)
        lvl_out.append(stats.n_out)
        lvl_ev.append(stats.n_evict)
    # Root packing: the last node's stream may hold duplicate keys (table +
    # BPE overlap — see kvagg.TwoLevelResult); combine exactly on carried
    # values BEFORE finalize (a finalized mean cannot be re-combined).
    packed = kvagg.sorted_combine(k, v, op=plan.op)
    return KVTreeResult(packed.unique_keys,
                        aggop.finalize_values(packed.combined_values),
                        jnp.stack(lvl_in), jnp.stack(lvl_out),
                        jnp.stack(lvl_ev))


def make_kv_tree_aggregator(
    mesh,
    level_axes: tuple[str, ...],
    *,
    fpe_capacity: int,
    ways: int = 4,
    bpe: bool = True,
    op: str = "sum",
    plan: dataplane.CascadePlan | None = None,
) -> Callable:
    """jit-ready word-count aggregator: per-worker streams in, root stream out."""

    fn = functools.partial(
        kv_tree_aggregate,
        level_axes=level_axes,
        fpe_capacity=fpe_capacity,
        ways=ways,
        bpe=bpe,
        op=op,
        plan=plan,
    )
    spec = P(level_axes)
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=KVTreeResult(P(), P(), P(), P(), P()),
        axis_names=set(level_axes),
        check_vma=False,
    )
    return jax.jit(mapped)
