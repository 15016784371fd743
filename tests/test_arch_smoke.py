"""Per-architecture smoke tests on reduced configs (CPU, 1 device).

Two layers of checking per assigned arch:
  1. train-step smoke: one real optimizer step; finite loss, params move.
  2. decode consistency: prefill + step-by-step decode reproduces the dense
     forward's logits at every decoded position (validates KV/SSM caches,
     ring buffers, RoPE offsets, MLA latents, hybrid interleave).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.reduced import reduced_config
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models.layers import lm_logits, rms_norm
from repro.models.model import LMModel
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, adamw_init, make_lr_schedule
from repro.train.step import TrainProfile, build_train_step

ARCHS = list(configs.ARCH_IDS)


def _cfg(arch):
    cfg = reduced_config(arch)
    over = {"dtype": "float32"}
    if cfg.moe is not None:  # no token drops -> decode matches dense exactly
        over["moe"] = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    return dataclasses.replace(cfg, **over)


def _batch(cfg, b=2, s=16, seed=0):
    data = SyntheticLMData(cfg, DataConfig(seq_len=s, global_batch=b, seed=seed))
    return data.batch_at(0)


def _dense_logits(model, params, batch):
    """All-position logits of the dense forward (ground truth)."""
    cfg = model.cfg
    x = model._embed_inputs(params, batch)
    opt = dataclasses.replace(model.opt, prefix_len=cfg.prefix_tokens, remat="none")
    x, _, _ = tfm.run_stack_dense(x, params, cfg, model.policy, opt)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params.get("head", params["embed"])
    return lm_logits(x, table, cfg.logit_softcap, cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = _cfg(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    # chunks of 8 divide both 16 (text) and 16+8 (vision-prefixed) sequences
    prof = TrainProfile(q_chunk=8, k_chunk=8, moe_token_chunk=32, remat="none")
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt_cfg = AdamWConfig()
    step_fn, shardings, _ = build_train_step(
        cfg, mesh, prof, opt_cfg, make_lr_schedule(1e-3, 2, 10),
        batch_example=batch, params_example=params,
    )
    opt_state = adamw_init(params, opt_cfg)
    p0 = jax.tree.map(lambda x: np.asarray(x).copy(), params)
    new_params, new_opt, metrics = step_fn(params, opt_state, batch,
                                           jnp.zeros((), jnp.int32))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params moved
    moved = jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        p0, jax.tree.map(np.asarray, new_params))
    assert max(jax.tree.leaves(moved)) > 0
    # a second step with the same shapes reuses the compiled fn and stays finite
    _, _, m2 = step_fn(new_params, new_opt, _batch(cfg, seed=1),
                       jnp.ones((), jnp.int32))
    assert np.isfinite(float(m2["loss"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_consistency(arch):
    """prefill + decode == dense forward, position by position."""
    cfg = _cfg(arch)
    model = LMModel(cfg, opt=tfm.ApplyOptions(q_chunk=8, k_chunk=8,
                                              moe_token_chunk=64, remat="none"))
    params = model.init(jax.random.PRNGKey(1))
    b, total, n_pre = 2, 16, 8
    batch = _batch(cfg, b=b, s=total, seed=2)
    want = np.asarray(_dense_logits(model, params, batch))  # [B, S(+pre), V]

    audio = cfg.frontend == "audio_stub"
    vision = cfg.frontend == "vision_stub"
    pre_batch = dict(batch)
    if audio:
        pre_batch = {"frame_embeds": batch["frame_embeds"][:, :n_pre]}
    else:
        pre_batch["tokens"] = batch["tokens"][:, :n_pre]
        pre_batch.pop("labels", None)
    cache_len = total + cfg.prefix_tokens

    logits, caches = jax.jit(
        lambda p, bb: model.prefill(p, bb, cache_len)
    )(params, pre_batch)
    np.testing.assert_allclose(
        np.asarray(logits)[:, 0],
        want[:, cfg.prefix_tokens + n_pre - 1],
        atol=2e-3, rtol=1e-3,
    )

    step = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    for k in range(n_pre, total):
        if audio:
            tok = batch["frame_embeds"][:, k:k + 1]
        else:
            tok = batch["tokens"][:, k:k + 1]
        cur = jnp.asarray(cfg.prefix_tokens + k, jnp.int32)
        logits, caches = step(params, tok, caches, cur)
        np.testing.assert_allclose(
            np.asarray(logits)[:, 0], want[:, cfg.prefix_tokens + k],
            atol=2e-3, rtol=1e-3,
            err_msg=f"{arch}: decode mismatch at position {k}",
        )


def test_decode_ring_buffer_wraps():
    """gemma2 local layers: decoding past the window wraps the ring buffer."""
    cfg = dataclasses.replace(_cfg("gemma2-27b"), window=8)
    model = LMModel(cfg, opt=tfm.ApplyOptions(q_chunk=8, k_chunk=8, remat="none"))
    params = model.init(jax.random.PRNGKey(3))
    b, total, n_pre = 1, 24, 8
    batch = _batch(cfg, b=b, s=total, seed=3)
    want = np.asarray(_dense_logits(model, params, batch))
    pre = {"tokens": batch["tokens"][:, :n_pre]}
    logits, caches = model.prefill(params, pre, total)
    step = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    for k in range(n_pre, total):  # wraps at k = 8 + window
        tok = batch["tokens"][:, k:k + 1]
        logits, caches = step(params, tok, caches, jnp.asarray(k, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits)[:, 0], want[:, k], atol=2e-3, rtol=1e-3,
            err_msg=f"ring-buffer mismatch at pos {k}",
        )


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_published_size(arch):
    """Config fidelity: param_count() lands near the architecture's name."""
    published = {
        "gemma2-27b": 27e9, "phi4-mini-3.8b": 3.8e9, "gemma3-4b": 4e9,
        "qwen3-32b": 32e9, "jamba-1.5-large-398b": 398e9,
        "deepseek-v2-236b": 236e9, "olmoe-1b-7b": 7e9, "paligemma-3b": 3e9,
        "mamba2-780m": 780e6, "musicgen-medium": 1.5e9,
    }
    cfg = configs.get_config(arch)
    n = cfg.param_count()
    lo, hi = 0.72 * published[arch], 1.35 * published[arch]
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params vs published {published[arch]/1e9:.1f}B"


def test_active_params_less_than_total_for_moe():
    for arch in ("deepseek-v2-236b", "olmoe-1b-7b", "jamba-1.5-large-398b"):
        cfg = configs.get_config(arch)
        assert cfg.active_param_count() < 0.5 * cfg.param_count()
    # deepseek-v2: 21B active of 236B (paper)
    ds = configs.get_config("deepseek-v2-236b")
    assert 14e9 <= ds.active_param_count() <= 30e9
