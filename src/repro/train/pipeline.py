"""GPipe-style pipeline parallelism over a mesh axis (optional at 512 chips).

The assigned models fit on the production mesh with DP x TP (+FSDP), so the
default schedules do not use PP — but at 1000+-node scale (or >400B dense
models) a stage axis becomes necessary. This module provides the schedule
as a composable building block:

  * layers are split into ``n_stages`` contiguous groups; each stage's
    params live on one slice of the ``stage`` mesh axis;
  * a microbatch stream flows stage-to-stage via ``jax.lax.ppermute``
    (the TPU-native neighbor transfer — ICI point-to-point);
  * the classic GPipe bubble: stages idle for (S-1) of (M + S - 1) ticks;
    utilization = M / (M + S - 1), so callers pick M >> S.

Runs inside ``jax.shard_map`` manual over the stage axis. Exercised by
tests/drivers/pipeline_driver.py on an 8-device mesh; at production scale
the same function takes ``stage`` as the leading mesh axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def gpipe_forward(
    x_micro: jnp.ndarray,  # [M, mb, ...] microbatch stream (fed to stage 0)
    stage_fn: Callable,  # (stage_params, x) -> x — one stage's layers
    stage_params: Any,  # this stage's parameter shard
    *,
    axis: str,
    n_stages: int,
) -> jnp.ndarray:
    """Run the GPipe schedule; returns the stage-(S-1) output stream.

    Must be called inside shard_map manual over ``axis``. Each device holds
    ``stage_params`` for ITS stage; microbatches enter at stage 0 and the
    finished stream is broadcast back to all stages at the end.
    """
    m = x_micro.shape[0]
    sid = jax.lax.axis_index(axis)
    ticks = m + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf, outs = carry  # buf: activation resident on this stage
        mb_idx = t - sid  # which microbatch this stage sees at tick t
        active = (mb_idx >= 0) & (mb_idx < m)
        feed = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        cur = jnp.where(sid == 0, feed, buf)
        y = stage_fn(stage_params, cur)
        y = jnp.where(active, y, buf)  # idle ticks keep the buffer
        # the last stage emits its finished microbatch into the output slot
        out_idx = jnp.clip(mb_idx, 0, m - 1)
        emit = active & (sid == n_stages - 1)
        outs = jax.lax.cond(
            emit,
            lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
            lambda o: o,
            outs,
        )
        nxt = jax.lax.ppermute(y, axis, perm) if n_stages > 1 else y
        return (nxt, outs), None

    buf0 = jnp.zeros_like(x_micro[0])
    outs0 = jnp.zeros_like(x_micro)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # results live on the last stage; hand every stage the same stream
    # (zero-mask + psum = broadcast from the last stage)
    if n_stages > 1:
        outs = jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
    return outs


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe idle fraction: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def make_gpipe_fn(mesh, stage_axis: str, n_stages: int, stage_fn: Callable):
    """jit-ready wrapper: (stacked_stage_params, x_micro) -> outputs.

    ``stacked_stage_params``: every leaf has leading dim n_stages, sharded
    over the stage axis (prefix spec); ``x_micro`` [M, mb, ...] replicated.
    """

    def region(params_stacked, x_micro):
        mine = jax.tree.map(lambda p: p[0], params_stacked)  # local stage
        return gpipe_forward(x_micro, stage_fn, mine, axis=stage_axis,
                             n_stages=n_stages)

    mapped = jax.shard_map(
        region,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),  # prefix spec for the params pytree
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )
    return jax.jit(mapped)
