"""Compressed (top-k KV + FPE/BPE) gradient exchange, end to end.

Checks: (a) k_fraction=1 + no-FPE == exact TREE numerics; (b) with real
compression (k=5%) + bounded-memory node training still converges and the
error-feedback residuals stay bounded. 8 fake CPU devices.
"""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.reduced import reduced_config
from repro.core.collectives import GradAggMode
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.optim import AdamWConfig, adamw_init, make_lr_schedule
from repro.train.compressed import build_compressed_train_step
from repro.train.step import TrainProfile, build_train_step

assert jax.device_count() == 8

CFG = dataclasses.replace(reduced_config("phi4-mini-3.8b"), dtype="float32")
DATA = SyntheticLMData(CFG, DataConfig(seq_len=16, global_batch=8, seed=0))
OPT = AdamWConfig(master_fp32=False)
LR = make_lr_schedule(1e-3, 2, 100)
MESH = make_mesh((2, 2, 2), ("data", "pod", "model"))
PROF = TrainProfile(dp_axes=("data", "pod"), tp_axis="model",
                    q_chunk=16, k_chunk=16, moe_token_chunk=16,
                    remat="none", mode=GradAggMode.TREE_COMPRESS)


def build_compressed(k_fraction, fpe_capacity):
    model = LMModel(CFG)
    params = model.init(jax.random.PRNGKey(0))
    step_fn, sh = build_compressed_train_step(
        CFG, MESH, PROF, OPT, LR,
        batch_example=DATA.batch_at(0), params_example=params,
        k_fraction=k_fraction, fpe_capacity=fpe_capacity,
    )
    params = jax.device_put(params, sh["params"])
    opt = jax.jit(lambda p: adamw_init(p, OPT), out_shardings=sh["opt"])(params)
    res = jax.device_put(sh["res_example"], sh["residuals"])
    return step_fn, params, opt, res


def build_exact_tree():
    prof = dataclasses.replace(PROF, mode=GradAggMode.TREE)
    model = LMModel(CFG)
    params = model.init(jax.random.PRNGKey(0))
    step_fn, sh, _ = build_train_step(
        CFG, MESH, prof, OPT, LR,
        batch_example=DATA.batch_at(0), params_example=params)
    params = jax.device_put(params, sh["params"])
    opt = jax.jit(lambda p: adamw_init(p, OPT), out_shardings=sh["opt"])(params)
    return step_fn, params, opt


def check_lossless_limit():
    """k = 100% of each shard and no FPE cap: exchange must be exact."""
    step_c, p_c, o_c, r_c = build_compressed(k_fraction=1.0, fpe_capacity=0)
    step_t, p_t, o_t = build_exact_tree()
    for i in range(3):
        b = DATA.batch_at(i)
        si = jnp.asarray(i, jnp.int32)
        p_c, o_c, r_c, m_c = step_c(p_c, o_c, r_c, b, si)
        p_t, o_t, m_t = step_t(p_t, o_t, b, si)
        assert abs(float(m_c["loss"]) - float(m_t["loss"])) < 2e-4, (
            i, float(m_c["loss"]), float(m_t["loss"]))
    for a, b_ in zip(jax.tree.leaves(jax.tree.map(np.asarray, p_c)),
                     jax.tree.leaves(jax.tree.map(np.asarray, p_t))):
        np.testing.assert_allclose(a, b_, atol=3e-4, rtol=1e-3)
    # nothing withheld when k is full
    assert max(float(jnp.max(jnp.abs(l))) for l in jax.tree.leaves(r_c)) < 1e-5
    print("lossless limit OK")


def check_real_compression_converges():
    step_c, p, o, r = build_compressed(k_fraction=0.05, fpe_capacity=64)
    losses = []
    res_norm = []
    for i in range(8):
        b = DATA.batch_at(i % 2)  # small cycling set -> clear loss decrease
        p, o, r, m = step_c(p, o, r, b, jnp.asarray(i, jnp.int32))
        losses.append(float(m["loss"]))
        res_norm.append(max(float(jnp.linalg.norm(l)) for l in jax.tree.leaves(r)))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses
    assert res_norm[-1] < 10 * (res_norm[0] + 1e-3), res_norm  # bounded EF
    print(f"compressed training converges OK: {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    check_lossless_limit()
    check_real_compression_converges()
    print("ALL OK")
