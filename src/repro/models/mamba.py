"""Mamba2 (SSD — state-space duality) mixer: chunked train, recurrent decode.

TPU mapping: the SSD chunked form is used for training/prefill — all the
heavy work is batched matmuls (intra-chunk attention-like products and
chunk-state outer products) that map onto the MXU; the O(S) recurrence only
runs across chunk boundaries (S/chunk scan steps).  Heads are independent,
so tensor parallelism shards the head dimension over the model axis
(B/C groups are small and replicated).

Decode is the O(1) recurrent update over a [B, H, P, N] state — no KV
cache, which is why the SSM/hybrid archs own the 500k-token decode cells.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import rms_norm


def init_mamba_params(key, cfg: ModelConfig, dtype) -> dict:
    mc = cfg.mamba
    d = cfg.d_model
    d_in = cfg.d_inner_mamba
    h = cfg.n_mamba_heads
    gn = mc.n_groups * mc.d_state
    conv_dim = d_in + 2 * gn
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * d_in + 2 * gn + h), dtype) * s,
        "conv_w": jax.random.normal(ks[1], (mc.conv_width, conv_dim), dtype) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))).astype(jnp.float32),
        "gate_norm": jnp.zeros((d_in,), dtype),
        "out_proj": jax.random.normal(ks[2], (d_in, d), dtype) * (d_in ** -0.5),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    mc = cfg.mamba
    d_in = cfg.d_inner_mamba
    gn = mc.n_groups * mc.d_state
    z, xin, bc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in, 2 * d_in + 2 * gn], axis=-1)
    b_, c_ = jnp.split(bc, 2, axis=-1)
    return z, xin, b_, c_, dt


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-channel causal conv, u [B,S,C], w [W,C]."""
    width = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(width):  # width is 4 — unrolled taps stay fused
        out = out + pad[:, i : i + u.shape[1], :] * w[i]
    return out + b


class MambaState(NamedTuple):
    ssm: jnp.ndarray  # [B, H, P, N] f32
    conv: jnp.ndarray  # [B, W-1, conv_dim]


def init_mamba_state(b, cfg: ModelConfig, dtype) -> MambaState:
    mc = cfg.mamba
    h, p_, n = cfg.n_mamba_heads, mc.head_dim, mc.d_state
    conv_dim = cfg.d_inner_mamba + 2 * mc.n_groups * mc.d_state
    return MambaState(
        ssm=jnp.zeros((b, h, p_, n), jnp.float32),
        conv=jnp.zeros((b, mc.conv_width - 1, conv_dim), dtype),
    )


def mamba_dense(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    """Full-sequence SSD pass.  x [B, S, D] -> [B, S, D]."""
    mc = cfg.mamba
    bsz, s, _ = x.shape
    h, pd, n, g, q = cfg.n_mamba_heads, mc.head_dim, mc.d_state, mc.n_groups, mc.chunk
    q = min(q, s)
    assert s % q == 0, (s, q)
    nc = s // q
    hpg = h // g  # heads per group

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])
    z, xin, b_, c_, dt = _split_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([xin, b_, c_], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xin, b_, c_ = jnp.split(xbc, [cfg.d_inner_mamba, cfg.d_inner_mamba + g * n], axis=-1)

    xh = xin.reshape(bsz, s, h, pd).astype(jnp.float32)
    bh = b_.reshape(bsz, s, g, n).astype(jnp.float32)
    ch = c_.reshape(bsz, s, g, n).astype(jnp.float32)
    dt_f = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    a = -jnp.exp(p["A_log"])  # [H]
    la = dt_f * a  # log decay per step [B,S,H]

    # chunk views; expand B/C groups to heads (head h lives in group h // hpg)
    xc = xh.reshape(bsz, nc, q, h, pd)
    bc_ = jnp.repeat(bh, hpg, axis=2).reshape(bsz, nc, q, h, n)
    cc = jnp.repeat(ch, hpg, axis=2).reshape(bsz, nc, q, h, n)
    dtc = dt_f.reshape(bsz, nc, q, h)
    lac = la.reshape(bsz, nc, q, h)
    csum = jnp.cumsum(lac, axis=2)  # [B,nc,Q,H]

    # ---- chunk states: S_c = sum_j exp(csum_end - csum_j) dt_j B_j x_j^T
    decay_end = jnp.exp(csum[:, :, -1:, :] - csum)  # [B,nc,Q,H]
    bx = jnp.einsum(
        "bcqhn,bcqhp,bcqh->bchpn",
        bc_, xc, dtc * decay_end,
        preferred_element_type=jnp.float32,
    )  # [B,nc,H,P,N]

    # ---- inter-chunk recurrence
    chunk_decay = jnp.exp(csum[:, :, -1, :])  # [B,nc,H]

    def scan_fn(s_run, inp):
        bx_c, dec_c = inp  # [B,H,P,N], [B,H]
        s_prev = s_run
        s_new = s_run * dec_c[..., None, None] + bx_c
        return s_new, s_prev

    s0 = jnp.zeros((bsz, h, pd, n), jnp.float32)
    _, s_prevs = jax.lax.scan(
        scan_fn,
        s0,
        (bx.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )  # s_prevs [nc, B, H, P, N] = state entering each chunk
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]

    # ---- intra-chunk (diagonal) + inter-chunk (off-diagonal) outputs
    cb = jnp.einsum("bcihn,bcjhn->bchij", cc, bc_, preferred_element_type=jnp.float32)
    # decay matrix per head: exp(csum_i - csum_j), causal (i >= j).  Mask
    # BEFORE the exp: above the diagonal csum_i - csum_j grows with the
    # chunk and overflows, and the backward pass of a masked inf is NaN.
    mask = jnp.tril(jnp.ones((q, q), bool))
    dmat = jnp.exp(jnp.where(mask[None, None, :, :, None],
                             csum[:, :, :, None, :] - csum[:, :, None, :, :],
                             -jnp.inf))  # [B,nc,Qi,Qj,H]
    att = cb * jnp.moveaxis(dmat, -1, 2)  # [B,nc,H,Qi,Qj]
    att = att * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]  # dt_j on the j axis
    y_diag = jnp.einsum(
        "bchij,bcjhp->bcihp", att, xc, preferred_element_type=jnp.float32
    )  # [B,nc,Q,H,P]

    decay_start = jnp.exp(csum)  # [B,nc,Q,H]
    y_off = jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp",
        cc, s_prevs, decay_start,
        preferred_element_type=jnp.float32,
    )

    y = (y_diag + y_off).reshape(bsz, s, h, pd) + xh * p["D"][None, None, :, None]
    y = y.reshape(bsz, s, cfg.d_inner_mamba).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["gate_norm"], cfg.norm_eps)
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"])


def mamba_decode(
    x: jnp.ndarray,  # [B, 1, D]
    p: dict,
    state: MambaState,
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, MambaState]:
    mc = cfg.mamba
    bsz = x.shape[0]
    h, pd, n, g = cfg.n_mamba_heads, mc.head_dim, mc.d_state, mc.n_groups
    hpg = h // g

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])[:, 0]  # [B, K]
    d_in = cfg.d_inner_mamba
    gn = g * n
    z, xin, b_, c_, dt = (
        zxbcdt[:, :d_in],
        zxbcdt[:, d_in : 2 * d_in],
        zxbcdt[:, 2 * d_in : 2 * d_in + gn],
        zxbcdt[:, 2 * d_in + gn : 2 * d_in + 2 * gn],
        zxbcdt[:, 2 * d_in + 2 * gn :],
    )
    xbc = jnp.concatenate([xin, b_, c_], axis=-1)  # [B, conv_dim]
    conv_in = jnp.concatenate([state.conv, xbc[:, None, :]], axis=1)  # [B, W, C]
    conv_out = jnp.einsum("bwc,wc->bc", conv_in, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = conv_in[:, 1:, :]

    xin, b_, c_ = (
        conv_out[:, :d_in],
        conv_out[:, d_in : d_in + gn],
        conv_out[:, d_in + gn :],
    )
    xh = xin.reshape(bsz, h, pd).astype(jnp.float32)
    bh = b_.reshape(bsz, g, n).astype(jnp.float32)
    ch = c_.reshape(bsz, g, n).astype(jnp.float32)
    dt_f = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    decay = jnp.exp(dt_f * (-jnp.exp(p["A_log"])))  # [B,H]

    bh_h = jnp.repeat(bh, hpg, axis=1)  # [B,H,N]
    ch_h = jnp.repeat(ch, hpg, axis=1)
    upd = jnp.einsum("bhp,bhn->bhpn", xh * dt_f[..., None], bh_h)
    new_ssm = state.ssm * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_ssm, ch_h) + xh * p["D"][None, :, None]
    y = y.reshape(bsz, 1, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)[:, None, :],
                 p["gate_norm"], cfg.norm_eps)
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"]), MambaState(new_ssm, new_conv)
