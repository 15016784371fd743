"""Attention: GQA/MQA/MHA + local(sliding) windows + MLA, train & decode.

Memory discipline (TPU): full score matrices are never materialized —
training/prefill uses chunked flash-style accumulation (nested lax.scan,
f32 running max/sum), decode uses either head-sharded einsums (when
n_kv_heads divides the model axis) or a shard_map flash-decode over a
sequence-sharded KV cache (partial softmax + psum combine) — the SP path
that makes 500k-token caches feasible.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLAConfig, ModelConfig
from .layers import apply_rope, rms_norm, softcap

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Sharding policy threaded through model apply.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """How activations/caches are laid out on the mesh at apply time."""

    mesh: object | None = None
    dp_axes: tuple[str, ...] = ()  # batch axes ('pod','data')
    tp_axis: str | None = None  # 'model'
    # decode: shard the KV-cache sequence dim over these axes (flash-decode)
    cache_seq_axes: tuple[str, ...] = ()
    # False when the global batch is too small to shard over dp_axes
    # (e.g. long_500k has batch=1): activations replicate over dp, but
    # weight storage/gather still uses dp_axes.
    batch_sharded: bool = True
    # sequence-parallel: shard inter-layer activations over tp_axis (SP)
    seq_shard: bool = False

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return self.dp_axes if self.batch_sharded else ()

    @property
    def distributed(self) -> bool:
        return self.mesh is not None

    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[self.tp_axis]


# ---------------------------------------------------------------------------
# Parameter init.
# ---------------------------------------------------------------------------


def init_attn_params(key, cfg: ModelConfig, dtype) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, hq, hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, hkv, hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, hkv, hd), dtype) * s,
        "wo": jax.random.normal(k4, (hq, hd, d), dtype) * ((hq * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_mla_params(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    assert m is not None
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    qd = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq_a": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * s,
        "q_a_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": jax.random.normal(ks[1], (m.q_lora_rank, h, qd), dtype)
        * (m.q_lora_rank ** -0.5),
        "wkv_a": jax.random.normal(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), dtype) * s,
        "kv_a_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": jax.random.normal(ks[3], (h, m.kv_lora_rank, m.qk_nope_dim), dtype)
        * (m.kv_lora_rank ** -0.5),
        "w_uv": jax.random.normal(ks[4], (h, m.kv_lora_rank, m.v_head_dim), dtype)
        * (m.kv_lora_rank ** -0.5),
        "wo": jax.random.normal(ks[5], (h, m.v_head_dim, d), dtype)
        * ((h * m.v_head_dim) ** -0.5),
    }


# ---------------------------------------------------------------------------
# Chunked flash-style attention (training / prefill).
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, window: int) -> jnp.ndarray:
    """[..., q, k] additive mask: causal + optional sliding window."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


class _KVChunks(NamedTuple):
    """Provider of K/V chunks — lets MLA expand lazily per chunk."""

    n_chunks: int
    chunk_len: int
    get: Callable  # j -> (k [B,c,KV,hdk], v [B,c,KV,hdv])


def _flash_over_kv(
    q: jnp.ndarray,  # [B, qc, KV, rep, hdk]  (f32-scaled already)
    kv: _KVChunks,
    q_pos: jnp.ndarray,  # [qc]
    *,
    window: int,
    cap: float,
    hdv: int,
) -> jnp.ndarray:
    b, qc, n_kv, rep, hdk = q.shape

    def step(carry, j):
        m, l, acc = carry
        k, v = kv.get(j)  # [B, c, KV, hdk/hdv]
        k_pos = j * kv.chunk_len + jnp.arange(kv.chunk_len)
        s = jnp.einsum(
            "bqkrh,bckh->bkrqc", q, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        s = softcap(s, cap)
        s = s + _mask_bias(q_pos, k_pos, window)  # [qc, c] broadcast
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkrqc,bckh->bkrqh", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, n_kv, rep, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, rep, qc), jnp.float32)
    a0 = jnp.zeros((b, n_kv, rep, qc, hdv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(kv.n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # [B, KV, rep, qc, hdv] -> [B, qc, KV*rep, hdv]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, qc, n_kv * rep, hdv)


def chunked_attention(
    q: jnp.ndarray,  # [B, S, H, hdk]
    kv: _KVChunks,
    *,
    n_kv_heads: int,
    scale: float,
    window: int = 0,
    cap: float = 0.0,
    q_chunk: int = 512,
    hdv: int | None = None,
) -> jnp.ndarray:
    b, s, h, hdk = q.shape
    hdv = hdv if hdv is not None else hdk
    rep = h // n_kv_heads
    q_chunk = min(q_chunk, s)
    assert s % q_chunk == 0, (s, q_chunk)
    nq = s // q_chunk
    qs = (q.astype(jnp.float32) * scale).reshape(b, nq, q_chunk, n_kv_heads, rep, hdk)

    def one_q(j):
        qp = j * q_chunk + jnp.arange(q_chunk)
        return _flash_over_kv(
            qs[:, j], kv, qp, window=window, cap=cap, hdv=hdv
        )

    if nq == 1:
        out = one_q(0)[:, None]
    else:
        out = jax.lax.map(one_q, jnp.arange(nq)).transpose(1, 0, 2, 3, 4)
    return out.reshape(b, s, h, hdv)


def kv_chunks_from_arrays(k: jnp.ndarray, v: jnp.ndarray, chunk: int) -> _KVChunks:
    b, s, n_kv, hd = k.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)

    def get(j):
        kj = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, axis=1)
        return kj, vj

    return _KVChunks(n_chunks=s // chunk, chunk_len=chunk, get=get)


# ---------------------------------------------------------------------------
# Standard (GQA) attention layer — dense pass.
# ---------------------------------------------------------------------------


def attn_dense(
    x: jnp.ndarray,  # [B, S, D]
    p: dict,
    cfg: ModelConfig,
    *,
    window: int,
    q_chunk: int = 512,
    k_chunk: int = 1024,
) -> jnp.ndarray:
    b, s, d = x.shape
    pos = jnp.arange(s)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5
    out = chunked_attention(
        q,
        kv_chunks_from_arrays(k, v, k_chunk),
        n_kv_heads=cfg.n_kv_heads,
        scale=scale,
        window=window,
        cap=cfg.attn_softcap,
        q_chunk=q_chunk,
    )
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) — dense pass with lazy per-chunk KV expansion.
# ---------------------------------------------------------------------------


def mla_dense(
    x: jnp.ndarray,
    p: dict,
    cfg: ModelConfig,
    *,
    q_chunk: int = 512,
    k_chunk: int = 512,
) -> jnp.ndarray:
    m = cfg.mla
    b, s, d = x.shape
    pos = jnp.arange(s)
    h = cfg.n_heads

    qa = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_a_norm"], cfg.norm_eps)
    qb = jnp.einsum("bsr,rhk->bshk", qa, p["wq_b"])
    q_nope, q_rope = jnp.split(qb, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)  # [B,S,H,nope+rope]

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope_raw = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope_raw[:, :, None, :], pos, cfg.rope_theta)  # [B,S,1,rope]

    chunk = min(k_chunk, s)
    assert s % chunk == 0

    def get(j):
        c = jax.lax.dynamic_slice_in_dim(c_kv, j * chunk, chunk, axis=1)
        kr = jax.lax.dynamic_slice_in_dim(k_rope, j * chunk, chunk, axis=1)
        k_nope = jnp.einsum("bsc,hcn->bshn", c, p["w_uk"])
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr, (b, chunk, h, m.qk_rope_dim))], axis=-1
        )
        v = jnp.einsum("bsc,hcv->bshv", c, p["w_uv"])
        return k_full, v

    kv = _KVChunks(n_chunks=s // chunk, chunk_len=chunk, get=get)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out = chunked_attention(
        q, kv, n_kv_heads=h, scale=scale, window=0, cap=cfg.attn_softcap,
        q_chunk=q_chunk, hdv=m.v_head_dim,
    )
    return jnp.einsum("bshv,hvd->bsd", out.astype(x.dtype), p["wo"])


# ---------------------------------------------------------------------------
# Decode: KV caches + single-token attention.
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jnp.ndarray  # [B, S_cache, KV, hd]
    v: jnp.ndarray  # [B, S_cache, KV, hd]
    pos: jnp.ndarray  # [S_cache] int32 absolute positions, -1 = empty


class MLACache(NamedTuple):
    c_kv: jnp.ndarray  # [B, S_cache, kv_lora]
    k_rope: jnp.ndarray  # [B, S_cache, rope_dim]
    pos: jnp.ndarray  # [S_cache]


def init_kv_cache(b, s_cache, n_kv, hd, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((b, s_cache, n_kv, hd), dtype),
        v=jnp.zeros((b, s_cache, n_kv, hd), dtype),
        pos=jnp.full((s_cache,), -1, jnp.int32),
    )


def init_mla_cache(b, s_cache, m: MLAConfig, dtype) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((b, s_cache, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((b, s_cache, m.qk_rope_dim), dtype),
        pos=jnp.full((s_cache,), -1, jnp.int32),
    )


def _flash_decode_local(q, kc, vc, kpos, cur_pos, *, scale, cap, window, axes):
    """Per-shard partial attention + cross-shard softmax combine.

    q [B,1,KV,rep,hd]; kc/vc [B,S_loc,KV,hd]; kpos [S_loc].
    Valid keys: pos in [cur_pos-window+1, cur_pos], pos >= 0.
    """
    s = jnp.einsum(
        "bqkrh,bskh->bkrqs", q.astype(jnp.float32) * scale, kc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    s = softcap(s, cap)
    ok = (kpos >= 0) & (kpos <= cur_pos)
    if window:
        ok &= kpos > cur_pos - window
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if axes:
        m = jax.lax.pmax(m, axes)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkrqs,bskh->bkrqh", p, vc.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if axes:
        l = jax.lax.psum(l, axes)
        o = jax.lax.psum(o, axes)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    b, n_kv, rep, one, hd = out.shape
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, n_kv * rep, hd)


def decode_attn(
    x: jnp.ndarray,  # [B, 1, D]
    p: dict,
    cache: KVCache,
    cur_pos: jnp.ndarray,  # [] int32: position of the new token
    cfg: ModelConfig,
    policy: ShardingPolicy,
    *,
    window: int,
) -> tuple[jnp.ndarray, KVCache]:
    b = x.shape[0]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], cfg.norm_eps)
    posv = cur_pos[None]
    q = apply_rope(q, jnp.broadcast_to(posv, (b, 1)), cfg.rope_theta)
    k_new = apply_rope(k_new, jnp.broadcast_to(posv, (b, 1)), cfg.rope_theta)

    s_cache = cache.k.shape[1]
    slot = (cur_pos % s_cache) if window else jnp.clip(cur_pos, 0, s_cache - 1)
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), slot, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), slot, axis=1),
        pos=jax.lax.dynamic_update_slice_in_dim(cache.pos, posv.astype(jnp.int32), slot, axis=0),
    )

    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    rep = cfg.n_heads // n_kv
    qr = q.reshape(b, 1, n_kv, rep, hd)

    axes = policy.cache_seq_axes
    if policy.distributed and axes:
        fn = functools.partial(
            _flash_decode_local, scale=scale, cap=cfg.attn_softcap,
            window=window, axes=axes,
        )
        # only the manual (cache-seq) axes appear in specs; batch sharding
        # over the dp axes stays auto and flows through untouched
        out = jax.shard_map(
            fn,
            mesh=policy.mesh,
            in_specs=(
                P(None, None, None, None, None),
                P(None, axes, None, None),
                P(None, axes, None, None),
                P(axes),
                P(),
            ),
            out_specs=P(None, None, None, None),
            axis_names=set(axes),
            check_vma=False,
        )(qr, new_cache.k, new_cache.v, new_cache.pos, cur_pos)
    else:
        out = _flash_decode_local(
            qr, new_cache.k, new_cache.v, new_cache.pos, cur_pos,
            scale=scale, cap=cfg.attn_softcap, window=window, axes=(),
        )
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache


def mla_decode(
    x: jnp.ndarray,  # [B, 1, D]
    p: dict,
    cache: MLACache,
    cur_pos: jnp.ndarray,
    cfg: ModelConfig,
    policy: ShardingPolicy,
) -> tuple[jnp.ndarray, MLACache]:
    """Absorbed-form MLA decode: attends directly over the compressed cache."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    posv = cur_pos[None]

    qa = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_a_norm"], cfg.norm_eps)
    qb = jnp.einsum("bsr,rhk->bshk", qa, p["wq_b"])
    q_nope, q_rope = jnp.split(qb, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, jnp.broadcast_to(posv, (b, 1)), cfg.rope_theta)
    # absorb W_UK into the query: [B,1,H,C]
    q_c = jnp.einsum("bshn,hcn->bshc", q_nope, p["w_uk"])

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_new, k_rope_raw = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_new = rms_norm(c_new, p["kv_a_norm"], cfg.norm_eps)
    k_rope_new = apply_rope(k_rope_raw[:, :, None, :], jnp.broadcast_to(posv, (b, 1)),
                            cfg.rope_theta)[:, :, 0, :]

    s_cache = cache.c_kv.shape[1]
    slot = jnp.clip(cur_pos, 0, s_cache - 1)
    new_cache = MLACache(
        c_kv=jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_new.astype(cache.c_kv.dtype), slot, axis=1),
        k_rope=jax.lax.dynamic_update_slice_in_dim(cache.k_rope, k_rope_new.astype(cache.k_rope.dtype), slot, axis=1),
        pos=jax.lax.dynamic_update_slice_in_dim(cache.pos, posv.astype(jnp.int32), slot, axis=0),
    )

    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

    def local_fn(q_c, q_r, ckv, krope, kpos, cur):
        s = jnp.einsum("bqhc,bsc->bhqs", q_c.astype(jnp.float32),
                       ckv.astype(jnp.float32), preferred_element_type=jnp.float32)
        s += jnp.einsum("bqhr,bsr->bhqs", q_r.astype(jnp.float32),
                        krope.astype(jnp.float32), preferred_element_type=jnp.float32)
        s *= scale
        ok = (kpos >= 0) & (kpos <= cur)
        s = jnp.where(ok, s, NEG_INF)
        mx = jnp.max(s, axis=-1)
        axes = policy.cache_seq_axes if policy.distributed else ()
        if axes:
            mx = jax.lax.pmax(mx, axes)
        pr = jnp.exp(s - mx[..., None])
        l = jnp.sum(pr, axis=-1)
        o = jnp.einsum("bhqs,bsc->bqhc", pr, ckv.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if axes:
            l = jax.lax.psum(l, axes)
            o = jax.lax.psum(o, axes)
        return o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]

    axes = policy.cache_seq_axes
    if policy.distributed and axes:
        o_c = jax.shard_map(
            local_fn,
            mesh=policy.mesh,
            in_specs=(
                P(None, None, None, None),
                P(None, None, None, None),
                P(None, axes, None),
                P(None, axes, None),
                P(axes),
                P(),
            ),
            out_specs=P(None, None, None, None),
            axis_names=set(axes),
            check_vma=False,
        )(q_c, q_rope, new_cache.c_kv, new_cache.k_rope, new_cache.pos, cur_pos)
    else:
        o_c = local_fn(q_c, q_rope, new_cache.c_kv, new_cache.k_rope, new_cache.pos, cur_pos)

    out_heads = jnp.einsum("bqhc,hcv->bqhv", o_c, p["w_uv"].astype(jnp.float32))
    y = jnp.einsum("bshv,hvd->bsd", out_heads.astype(x.dtype), p["wo"])
    return y, new_cache
