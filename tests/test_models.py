"""Layer-level model units: RoPE, norms, attention vs naive oracle, MoE."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig, MoEConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (
    apply_rope, cross_entropy, lm_logits, rms_norm, softcap,
)

F32 = jnp.float32


def _mini_cfg(**kw) -> ModelConfig:
    base = dict(
        name="mini", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=128,
        vocab_pad_multiple=32, dtype="float32",
        pattern=(LayerSpec(),),
    )
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# RoPE.
# ---------------------------------------------------------------------------


def test_rope_preserves_norm(rng):
    x = jnp.asarray(rng.standard_normal((2, 6, 4, 8)).astype(np.float32))
    y = apply_rope(x, jnp.arange(6), 10000.0)
    np.testing.assert_allclose(
        jnp.linalg.norm(x, axis=-1), jnp.linalg.norm(y, axis=-1), rtol=1e-5
    )


def test_rope_zero_position_is_identity(rng):
    x = jnp.asarray(rng.standard_normal((1, 1, 2, 8)).astype(np.float32))
    y = apply_rope(x, jnp.zeros((1,), jnp.int32), 10000.0)
    np.testing.assert_allclose(x, y, atol=1e-6)


def test_rope_relative_property(rng):
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))

    def dot_at(i, j):
        qi = apply_rope(q, jnp.asarray([i]), 10000.0)
        kj = apply_rope(k, jnp.asarray([j]), 10000.0)
        return float(jnp.sum(qi * kj))

    assert dot_at(5, 3) == pytest.approx(dot_at(9, 7), rel=1e-4)
    assert dot_at(0, 0) == pytest.approx(dot_at(11, 11), rel=1e-4)


# ---------------------------------------------------------------------------
# Norm / softcap / CE.
# ---------------------------------------------------------------------------


def test_rms_norm_matches_manual(rng):
    x = jnp.asarray(rng.standard_normal((3, 5, 16)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    got = rms_norm(x, g, 1e-6)
    want = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6) * (
        1 + np.asarray(g)
    )
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_softcap_bounded_and_monotone():
    x = jnp.linspace(-100, 100, 201)
    y = softcap(x, 30.0)
    assert float(jnp.max(jnp.abs(y))) <= 30.0
    assert np.all(np.diff(np.asarray(y)) >= 0)
    # no-op when cap == 0
    np.testing.assert_array_equal(softcap(x, 0.0), x)


def test_cross_entropy_matches_manual(rng):
    v = 16
    logits = jnp.asarray(rng.standard_normal((2, 3, v)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, v, (2, 3)).astype(np.int32))
    got = float(cross_entropy(logits, labels, v))
    lse = np.log(np.exp(np.asarray(logits)).sum(-1))
    picked = np.take_along_axis(np.asarray(logits), np.asarray(labels)[..., None], -1)[..., 0]
    want = float(np.mean(lse - picked))
    assert got == pytest.approx(want, rel=1e-5)


def test_lm_logits_masks_padded_vocab(rng):
    """Vocab padding rows must never receive probability mass."""
    cfg = _mini_cfg()
    table = jnp.asarray(rng.standard_normal((cfg.padded_vocab, cfg.d_model)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((1, 2, cfg.d_model)).astype(np.float32))
    logits = lm_logits(x, table, 0.0, cfg.vocab_size)
    assert logits.shape[-1] == cfg.padded_vocab
    pad = np.asarray(logits)[..., cfg.vocab_size:]
    assert np.all(pad < -1e9)


# ---------------------------------------------------------------------------
# Attention vs naive oracle.
# ---------------------------------------------------------------------------


def _naive_attention(q, k, v, *, n_kv, scale, window, cap):
    """Materialized causal (optionally windowed, softcapped) GQA attention."""
    b, s, h, hd = q.shape
    rep = h // n_kv
    kk = np.repeat(np.asarray(k), rep, axis=2)
    vv = np.repeat(np.asarray(v), rep, axis=2)
    scores = np.einsum("bqhk,bshk->bhqs", np.asarray(q) * scale, kk)
    if cap:
        scores = cap * np.tanh(scores / cap)
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    scores = np.where(mask[None, None], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqs,bshk->bqhk", p, vv)


@pytest.mark.parametrize("window,cap", [(0, 0.0), (4, 0.0), (0, 20.0), (4, 20.0)])
def test_attn_dense_matches_naive(window, cap, rng):
    cfg = _mini_cfg(attn_softcap=cap, window=window)
    key = jax.random.PRNGKey(0)
    p = attn.init_attn_params(key, cfg, F32)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)).astype(np.float32)) * 0.3
    got = attn.attn_dense(x, p, cfg, window=window, q_chunk=4, k_chunk=4)

    pos = jnp.arange(8)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = _naive_attention(q, k, v, n_kv=cfg.n_kv_heads,
                         scale=cfg.head_dim ** -0.5, window=window, cap=cap)
    want = np.einsum("bqhk,hkd->bqd", o, np.asarray(p["wo"]))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


def test_attn_chunk_invariance(rng):
    """Different q/k chunkings produce identical outputs (flash combine)."""
    cfg = _mini_cfg()
    p = attn.init_attn_params(jax.random.PRNGKey(1), cfg, F32)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.d_model)).astype(np.float32))
    outs = [
        attn.attn_dense(x, p, cfg, window=0, q_chunk=qc, k_chunk=kc)
        for qc, kc in [(16, 16), (4, 16), (16, 4), (8, 2), (2, 8)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=3e-6)


def test_gqa_equals_mha_when_kv_equals_heads(rng):
    """n_kv == n_heads reduces GQA to standard MHA."""
    cfg = _mini_cfg(n_kv_heads=4)
    p = attn.init_attn_params(jax.random.PRNGKey(2), cfg, F32)
    x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)).astype(np.float32))
    got = attn.attn_dense(x, p, cfg, window=0, q_chunk=8, k_chunk=8)
    assert got.shape == (1, 8, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(got)))


def test_qk_norm_applied(rng):
    cfg = _mini_cfg(qk_norm=True)
    p = attn.init_attn_params(jax.random.PRNGKey(3), cfg, F32)
    assert "q_norm" in p and "k_norm" in p
    x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)).astype(np.float32))
    out = attn.attn_dense(x, p, cfg, window=0, q_chunk=8, k_chunk=8)
    assert np.all(np.isfinite(np.asarray(out)))


# ---------------------------------------------------------------------------
# MoE vs dense-dispatch reference.
# ---------------------------------------------------------------------------


def _moe_cfg(cf=8.0):
    return _mini_cfg(
        family="moe",
        pattern=(LayerSpec(ffn="moe"),),
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, n_shared=1,
                      capacity_factor=cf),
    )


def test_moe_matches_reference(rng):
    cfg = _moe_cfg()
    p = moe_mod.init_moe_params(jax.random.PRNGKey(4), cfg, F32)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)).astype(np.float32)) * 0.5
    got, aux = moe_mod.moe_apply(x, p, cfg, attn.ShardingPolicy(), token_chunk=16)
    want = moe_mod.moe_ref(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)
    assert float(aux.load_balance) >= 0.0


def test_moe_chunk_invariance(rng):
    cfg = _moe_cfg()
    p = moe_mod.init_moe_params(jax.random.PRNGKey(5), cfg, F32)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)).astype(np.float32))
    o1, _ = moe_mod.moe_apply(x, p, cfg, attn.ShardingPolicy(), token_chunk=16)
    o2, _ = moe_mod.moe_apply(x, p, cfg, attn.ShardingPolicy(), token_chunk=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_moe_capacity_drops_tokens(rng):
    """Tiny capacity factor must drop tokens (outputs differ from cf=8)."""
    cfg_hi, cfg_lo = _moe_cfg(8.0), _moe_cfg(0.25)
    p = moe_mod.init_moe_params(jax.random.PRNGKey(6), cfg_hi, F32)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg_hi.d_model)).astype(np.float32))
    hi, _ = moe_mod.moe_apply(x, p, cfg_hi, attn.ShardingPolicy(), token_chunk=32)
    lo, _ = moe_mod.moe_apply(x, p, cfg_lo, attn.ShardingPolicy(), token_chunk=32)
    assert not np.allclose(np.asarray(hi), np.asarray(lo))


def test_moe_router_weights_normalized(rng):
    """Top-k router weights are a distribution over the selected experts."""
    cfg = _moe_cfg()
    p = moe_mod.init_moe_params(jax.random.PRNGKey(7), cfg, F32)
    x = jnp.asarray(rng.standard_normal((1, 4, cfg.d_model)).astype(np.float32))
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    w = jax.nn.softmax(logits, axis=-1)
    topw, _ = jax.lax.top_k(w, cfg.moe.top_k)
    assert np.all(np.asarray(topw.sum(-1)) <= 1.0 + 1e-6)


def test_mamba_gradient_finite_over_a_long_decaying_chunk():
    """Strong decay over a 256-step chunk overflows exp(csum_i - csum_j)
    above the diagonal; the masked entries must not turn the backward
    pass into NaN (they did at mamba2-780m's published chunk)."""
    from repro.configs.base import MambaConfig
    from repro.models import mamba

    cfg = _mini_cfg(family="ssm", n_heads=0, n_kv_heads=0, head_dim=0,
                    d_ff=0, pattern=(LayerSpec("mamba", "none"),),
                    mamba=MambaConfig(d_state=16, head_dim=16, chunk=256))
    p = mamba.init_mamba_params(jax.random.PRNGKey(0), cfg, F32)
    p["dt_bias"] = jnp.full_like(p["dt_bias"], 2.0)  # dt ~ 2 per step
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, cfg.d_model), F32)

    def loss(p, x):
        return jnp.sum(mamba.mamba_dense(x, p, cfg) ** 2)

    gp, gx = jax.grad(loss, argnums=(0, 1))(p, x)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(gp))
    assert bool(jnp.all(jnp.isfinite(gx)))
