"""Multi-device serving: TP + model-axis-sharded KV cache (flash-decode)
must reproduce the single-device decode exactly. 8 fake CPU devices."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.reduced import reduced_config
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.models import transformer as tfm
from repro.train.step import TrainProfile, build_prefill_step, build_serve_step

assert jax.device_count() == 8


def _oracle_decode(cfg, params, batch, n_pre, n_dec, cache_len):
    """Plain single-jit prefill+decode (no mesh)."""
    model = LMModel(cfg, opt=tfm.ApplyOptions(q_chunk=8, k_chunk=8, remat="none"))
    pre = {k: (v[:, :n_pre] if k in ("tokens", "frame_embeds") else v)
           for k, v in batch.items() if k != "labels"}
    logits, caches = jax.jit(lambda p, b: model.prefill(p, b, cache_len))(params, pre)
    toks = [np.asarray(jnp.argmax(logits[:, -1], -1))]
    step = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    cur = jnp.asarray(toks[-1][:, None], jnp.int32)
    for i in range(n_dec):
        lg, caches = step(params, cur, caches, jnp.asarray(cfg.prefix_tokens + n_pre + i, jnp.int32))
        cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        toks.append(np.asarray(cur[:, 0]))
    return np.stack(toks, 1)  # [B, 1+n_dec]


def check_sharded_decode(arch, batch_size, batch_shardable):
    cfg = dataclasses.replace(reduced_config(arch), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mesh = make_mesh((2, 4), ("data", "model"))
    prof = TrainProfile(dp_axes=("data",), tp_axis="model",
                        q_chunk=8, k_chunk=8, moe_token_chunk=64, remat="none")
    n_pre, n_dec, cache_len = 8, 5, 32
    data = SyntheticLMData(cfg, DataConfig(seq_len=16, global_batch=batch_size, seed=1))
    batch = data.batch_at(0)
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))

    want = _oracle_decode(cfg, params, batch, n_pre, n_dec,
                          cache_len + cfg.prefix_tokens)

    # distributed: prefill then serve steps with model-axis-sharded caches
    pre_batch = {k: (v[:, :n_pre] if k in ("tokens", "frame_embeds") else v)
                 for k, v in batch.items() if k != "labels"}
    prefill_fn, sh_p, _ = build_prefill_step(
        cfg, mesh, prof, cache_len=cache_len + cfg.prefix_tokens,
        batch_example=pre_batch, params_example=params,
        batch_shardable=batch_shardable, cache_seq_axes=("model",),
    )
    serve_fn, sh_s, _ = build_serve_step(
        cfg, mesh, prof, cache_len=cache_len + cfg.prefix_tokens,
        batch=batch_size, params_example=params,
        batch_shardable=batch_shardable, cache_seq_axes=("model",),
    )
    logits, caches = prefill_fn(params, pre_batch)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    got = [np.asarray(tok[:, 0])]
    for i in range(n_dec):
        tok, caches = serve_fn(params, caches, tok,
                               jnp.asarray(cfg.prefix_tokens + n_pre + i, jnp.int32))
        got.append(np.asarray(tok[:, 0]))
    got = np.stack(got, 1)
    np.testing.assert_array_equal(got, want)
    print(f"sharded decode OK: {arch} batch={batch_size} "
          f"shardable={batch_shardable} tokens={got[0].tolist()}")


if __name__ == "__main__":
    check_sharded_decode("gemma2-27b", 4, True)     # GQA + local/global + softcap
    check_sharded_decode("olmoe-1b-7b", 1, False)   # MoE, unshardable batch=1
    check_sharded_decode("deepseek-v2-236b", 4, True)  # MLA latent cache
    print("ALL OK")
