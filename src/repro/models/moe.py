"""Mixture-of-Experts with expert parallelism (EP over the model axis).

Production layout (DeepSeek-V2 / OLMoE / Jamba style):

  * experts sharded over ``model`` (EP): each model shard owns E/M experts;
  * expert weights additionally stored F-sharded over the data axes
    (ZeRO-3); they are all-gathered once per layer inside the manual region
    (explicit, overlappable with the token-chunk scan);
  * tokens stay in their data-parallel row; dispatch crosses only the
    ``model`` axis via two all_to_alls (out and back);
  * dispatch is scatter/gather based (NO one-hot dispatch einsums — those
    inflate HLO FLOPs by the capacity factor and wreck the roofline);
  * token-chunked scan bounds the transient send/recv/expert buffers;
  * capacity overflow drops choices (standard GShard token dropping) with
    the slack controlled by ``capacity_factor``.

The same code path runs on a single device (ep_size=1: all_to_alls are
identity) so unit tests exercise the identical dispatch math.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from .attention import ShardingPolicy
from .layers import activation, gated_mlp


def init_moe_params(key, cfg: ModelConfig, dtype) -> dict:
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff_expert, mo.n_experts
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * s,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * s,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * (f ** -0.5),
    }
    if mo.n_shared:
        fs = mo.n_shared * f
        p["ws_gate"] = jax.random.normal(ks[4], (d, fs), dtype) * s
        p["ws_up"] = jax.random.normal(ks[5], (d, fs), dtype) * s
        p["ws_down"] = jax.random.normal(ks[6], (fs, d), dtype) * (fs ** -0.5)
    return p


class MoEAux(NamedTuple):
    load_balance: jnp.ndarray  # scalar aux loss
    router_z: jnp.ndarray  # scalar z loss


def _dispatch_ffn(
    xf: jnp.ndarray,  # [T, D] local tokens
    gates: jnp.ndarray,  # [T, k]
    eidx: jnp.ndarray,  # [T, k] global expert ids
    wg, wu, wd,  # local experts [E_l, D, F]
    *,
    n_experts: int,
    ep_axis: str | None,
    ep_size: int,
    capacity_factor: float,
    act: str,
    token_chunk: int,
) -> jnp.ndarray:
    t, d = xf.shape
    k = gates.shape[-1]
    e_local = n_experts // ep_size
    token_chunk = min(token_chunk, t)
    assert t % token_chunk == 0, (t, token_chunk)
    n_chunks = t // token_chunk
    cap_send = int(-(-token_chunk * k * capacity_factor // ep_size))
    cap_exp = int(-(-token_chunk * k * capacity_factor // e_local))

    def chunk_fn(carry, j):
        xs = jax.lax.dynamic_slice_in_dim(xf, j * token_chunk, token_chunk, axis=0)
        gs = jax.lax.dynamic_slice_in_dim(gates, j * token_chunk, token_chunk, axis=0)
        es = jax.lax.dynamic_slice_in_dim(eidx, j * token_chunk, token_chunk, axis=0)
        n = token_chunk * k
        e_flat = es.reshape(n)
        g_flat = gs.reshape(n)
        tok_of = jnp.repeat(jnp.arange(token_chunk), k)

        dest = e_flat // e_local  # [n] target model shard
        oh_dest = (dest[:, None] == jnp.arange(ep_size)[None, :]).astype(jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(oh_dest, axis=0) - 1, dest[:, None], 1)[:, 0]
        # overflow -> rank >= cap_send -> scatter drops it
        send_x = jnp.zeros((ep_size, cap_send, d), xf.dtype).at[dest, rank].set(
            xs[tok_of], mode="drop"
        )
        send_e = jnp.full((ep_size, cap_send), -1, jnp.int32).at[dest, rank].set(
            (e_flat % e_local).astype(jnp.int32), mode="drop"
        )
        if ep_axis is not None and ep_size > 1:
            recv_x = jax.lax.all_to_all(send_x, ep_axis, split_axis=0, concat_axis=0, tiled=False)
            recv_e = jax.lax.all_to_all(send_e, ep_axis, split_axis=0, concat_axis=0, tiled=False)
        else:
            recv_x, recv_e = send_x, send_e

        rx = recv_x.reshape(ep_size * cap_send, d)
        re = recv_e.reshape(ep_size * cap_send)
        valid = re >= 0
        re_safe = jnp.where(valid, re, 0)
        oh_e = (jnp.where(valid, re, -1)[:, None] == jnp.arange(e_local)[None, :]).astype(jnp.int32)
        erank = jnp.take_along_axis(jnp.cumsum(oh_e, axis=0) - 1, re_safe[:, None], 1)[:, 0]
        erank = jnp.where(valid, erank, cap_exp)  # invalid -> dropped
        buf = jnp.zeros((e_local, cap_exp, d), xf.dtype).at[re_safe, erank].set(
            rx, mode="drop"
        )

        g = activation(
            jnp.einsum("ecd,edf->ecf", buf, wg, preferred_element_type=jnp.float32), act
        )
        u = jnp.einsum("ecd,edf->ecf", buf, wu, preferred_element_type=jnp.float32)
        h = jnp.einsum("ecf,efd->ecd", (g * u).astype(xf.dtype), wd,
                       preferred_element_type=jnp.float32).astype(xf.dtype)

        # gather results back into recv layout, a2a home, weighted-combine
        back = h[re_safe, jnp.clip(erank, 0, cap_exp - 1)]
        back = jnp.where((valid & (erank < cap_exp))[:, None], back, 0.0)
        back = back.reshape(ep_size, cap_send, d)
        if ep_axis is not None and ep_size > 1:
            ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0, tiled=False)
        else:
            ret = back
        y_choice = ret[dest, jnp.clip(rank, 0, cap_send - 1)]
        y_choice = jnp.where((rank < cap_send)[:, None], y_choice, 0.0)
        y_choice = y_choice * g_flat[:, None].astype(y_choice.dtype)
        y = jnp.zeros((token_chunk, d), xf.dtype).at[tok_of].add(y_choice.astype(xf.dtype))
        return carry, y

    _, ys = jax.lax.scan(chunk_fn, 0, jnp.arange(n_chunks))
    return ys.reshape(t, d)


def moe_apply(
    x: jnp.ndarray,  # [B, S, D]
    p: dict,
    cfg: ModelConfig,
    policy: ShardingPolicy,
    *,
    token_chunk: int = 4096,
) -> tuple[jnp.ndarray, MoEAux]:
    mo = cfg.moe
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, mo.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # aux losses (computed over the local logical batch; psum-free, the
    # mean is already a fine estimator and stays SPMD-friendly)
    me = jnp.mean(probs.reshape(-1, mo.n_experts), axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(eidx, mo.n_experts).sum(-2) > 0).astype(jnp.float32).reshape(
            -1, mo.n_experts
        ),
        axis=0,
    )
    aux = MoEAux(
        load_balance=mo.n_experts * jnp.sum(me * ce) * mo.aux_loss_weight,
        router_z=jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2) * mo.router_z_weight,
    )

    ep_axis = policy.tp_axis if policy.distributed else None
    ep_size = policy.tp_size() if policy.distributed else 1

    fn = functools.partial(
        _dispatch_ffn,
        n_experts=mo.n_experts,
        ep_axis=ep_axis,
        ep_size=ep_size,
        capacity_factor=mo.capacity_factor,
        act=cfg.act,
        token_chunk=token_chunk,
    )

    if policy.distributed and ep_size > 1:
        dpw = policy.dp_axes if policy.dp_axes else None  # weight storage
        dpb = policy.batch_axes if policy.batch_axes else None  # activations
        dp_lead = (dpb,) if dpb else ()
        tp = policy.tp_axis

        def region(xl, gl, el, wg, wu, wd):
            # ZeRO-3: gather the F-shard of expert weights over data axes
            if policy.dp_axes:
                wg = jax.lax.all_gather(wg, policy.dp_axes, axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, policy.dp_axes, axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, policy.dp_axes, axis=1, tiled=True)
            t_l = xl.shape[0] * xl.shape[1]
            xf = xl.reshape(t_l, d)
            gf = gl.reshape(t_l, -1)
            ef = el.reshape(t_l, -1)
            if t_l % ep_size == 0:
                # sequence-shard the tokens over the EP axis: each model
                # shard routes its own T/ep slice (SP x EP — no replicated
                # dispatch compute), outputs all-gathered back.
                t_m = t_l // ep_size
                start = jax.lax.axis_index(ep_axis) * t_m
                y = fn(
                    jax.lax.dynamic_slice_in_dim(xf, start, t_m, 0),
                    jax.lax.dynamic_slice_in_dim(gf, start, t_m, 0),
                    jax.lax.dynamic_slice_in_dim(ef, start, t_m, 0),
                    wg, wu, wd,
                )
                y = jax.lax.all_gather(y, ep_axis, axis=0, tiled=True)
            else:
                # tiny token counts (decode): replicated dispatch is cheaper
                # than padding to divisibility
                y = fn(xf, gf, ef, wg, wu, wd)
            return y.reshape(xl.shape)

        y = jax.shard_map(
            region,
            mesh=policy.mesh,
            in_specs=(
                P(*dp_lead, None, None),
                P(*dp_lead, None, None),
                P(*dp_lead, None, None),
                P(tp, None, dpw),
                P(tp, None, dpw),
                P(tp, dpw, None),
            ),
            out_specs=P(*dp_lead, None, None),
            axis_names=set((*policy.dp_axes, tp)),
            check_vma=False,
        )(x, gates.astype(x.dtype), eidx, p["w_gate"], p["w_up"], p["w_down"])
    else:
        y = fn(
            x.reshape(b * s, d),
            gates.astype(x.dtype).reshape(b * s, -1),
            eidx.reshape(b * s, -1),
            p["w_gate"], p["w_up"], p["w_down"],
        ).reshape(b, s, d)

    if mo.n_shared:
        y = y + gated_mlp(x, p["ws_gate"], p["ws_up"], p["ws_down"], cfg.act)
    return y, aux


def moe_ref(x, p, cfg) -> jnp.ndarray:
    """Dense per-expert reference (no capacity drops) for unit tests."""
    mo = cfg.moe
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, mo.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    y = jnp.zeros_like(x)
    for e in range(mo.n_experts):
        w = jnp.sum(jnp.where(eidx == e, gates, 0.0), axis=-1)  # [B,S]
        h = gated_mlp(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], cfg.act)
        y = y + h * w[..., None].astype(x.dtype)
    if mo.n_shared:
        y = y + gated_mlp(x, p["ws_gate"], p["ws_up"], p["ws_down"], cfg.act)
    return y
