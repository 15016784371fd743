"""End-to-end multi-device training: flat == tree == gather numerics,
checkpoint/restart mid-run, elastic re-mesh. 8 fake CPU devices."""

import os
import tempfile

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.reduced import reduced_config
from repro.core.collectives import GradAggMode
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.optim import AdamWConfig, adamw_init, make_lr_schedule
from repro.train.step import TrainProfile, build_train_step

assert jax.device_count() == 8

CFG = dataclasses.replace(
    reduced_config("olmoe-1b-7b"), dtype="float32")  # MoE: exercises EP a2a
DATA = SyntheticLMData(CFG, DataConfig(seq_len=16, global_batch=8, seed=0))
OPT = AdamWConfig(master_fp32=True)
LR = make_lr_schedule(1e-3, 2, 100)


def build(mesh, mode):
    prof = TrainProfile(
        dp_axes=tuple(a for a in ("pod", "data") if a in mesh.axis_names),
        tp_axis="model", q_chunk=16, k_chunk=16, moe_token_chunk=16,
        remat="none", mode=mode,
    )
    model = LMModel(CFG)
    params = model.init(jax.random.PRNGKey(0))
    step_fn, shardings, _ = build_train_step(
        CFG, mesh, prof, OPT, LR,
        batch_example=DATA.batch_at(0), params_example=params,
    )
    params = jax.device_put(params, shardings["params"])
    opt = jax.jit(lambda p: adamw_init(p, OPT),
                  out_shardings=shardings["opt"])(params)
    return step_fn, params, opt, shardings


def run_steps(step_fn, params, opt, start, n):
    losses = []
    for i in range(start, start + n):
        params, opt, m = step_fn(params, opt, DATA.batch_at(i),
                                 jnp.asarray(i, jnp.int32))
        losses.append(float(m["loss"]))
    return params, opt, losses


def check_modes_agree():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    results = {}
    for mode in (GradAggMode.FLAT, GradAggMode.TREE, GradAggMode.GATHER):
        step_fn, params, opt, _ = build(mesh, mode)
        params, opt, losses = run_steps(step_fn, params, opt, 0, 4)
        results[mode] = (jax.tree.map(np.asarray, params), losses)
        assert all(np.isfinite(l) for l in losses), (mode, losses)
    ref_p, ref_l = results[GradAggMode.FLAT]
    for mode in (GradAggMode.TREE, GradAggMode.GATHER):
        p, l = results[mode]
        np.testing.assert_allclose(l, ref_l, rtol=2e-4,
                                   err_msg=f"{mode} losses differ")
        for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(p)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    # training makes progress
    assert ref_l[-1] < ref_l[0], ref_l
    print(f"modes agree OK: losses {ref_l}")


def check_checkpoint_restart_and_elastic():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    step_fn, params, opt, shardings = build(mesh, GradAggMode.TREE)
    params, opt, l1 = run_steps(step_fn, params, opt, 0, 3)
    ckdir = tempfile.mkdtemp(prefix="ckpt_")
    mgr = CheckpointManager(ckdir, keep=2)
    mgr.save(2, {"params": params, "opt": opt})
    # continue the original
    params_a, opt_a, la = run_steps(step_fn, params, opt, 3, 3)

    # 'failure': rebuild from checkpoint on the SAME mesh
    step_fn2, params0, opt0, sh2 = build(mesh, GradAggMode.TREE)
    restored, manifest = mgr.restore({"params": params0, "opt": opt0})
    params_b = jax.device_put(restored["params"], sh2["params"])
    opt_b = jax.device_put(restored["opt"], sh2["opt"])
    params_b, opt_b, lb = run_steps(step_fn2, params_b, opt_b, 3, 3)
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.tree.map(np.asarray, params_a)),
                    jax.tree.leaves(jax.tree.map(np.asarray, params_b))):
        np.testing.assert_allclose(a, b, atol=1e-6)
    print(f"checkpoint restart OK: losses {lb}")

    # ELASTIC: restart the same checkpoint on a DIFFERENT mesh (no pod axis,
    # 4-wide data) — checkpoints are mesh-agnostic full arrays.
    mesh2 = make_mesh((4, 2), ("data", "model"))
    step_fn3, params0, opt0, sh3 = build(mesh2, GradAggMode.TREE)
    restored2, _ = mgr.restore({"params": params0, "opt": opt0})
    params_c = jax.device_put(restored2["params"], sh3["params"])
    opt_c = jax.device_put(restored2["opt"], sh3["opt"])
    params_c, opt_c, lc = run_steps(step_fn3, params_c, opt_c, 3, 3)
    np.testing.assert_allclose(lc, la, rtol=2e-4)  # same numerics on new mesh
    print(f"elastic re-mesh OK: losses {lc}")


if __name__ == "__main__":
    check_modes_agree()
    check_checkpoint_restart_and_elastic()
    print("ALL OK")
