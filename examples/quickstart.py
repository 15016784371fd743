"""Quickstart: train a tiny LM with the SwitchAgg tree exchange, then decode.

Runs on 1 CPU in ~a minute:
    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.reduced import reduced_config
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, adamw_init, make_lr_schedule
from repro.train.step import TrainProfile, build_train_step


def main():
    # a miniature gemma2 (local+global attention, softcaps) in float32
    cfg = dataclasses.replace(reduced_config("gemma2-27b"), dtype="float32")
    print(f"model: {cfg.name} | {cfg.param_count()/1e6:.2f}M params | "
          f"pattern {[s.mixer for s in cfg.pattern]}")

    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    prof = TrainProfile(q_chunk=16, k_chunk=16, moe_token_chunk=64, remat="none")
    data = SyntheticLMData(cfg, DataConfig(seq_len=32, global_batch=8))
    opt_cfg = AdamWConfig()
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))

    step_fn, sh, _ = build_train_step(
        cfg, mesh, prof, opt_cfg, make_lr_schedule(3e-3, 5, 60),
        batch_example=data.batch_at(0), params_example=params)
    opt = adamw_init(params, opt_cfg)

    # QUICKSTART_STEPS lets the CI smoke test run a short budget
    n_steps = int(os.environ.get("QUICKSTART_STEPS", "60"))
    print(f"training {n_steps} steps...")
    for i in range(n_steps):
        params, opt, m = step_fn(params, opt, data.batch_at(i),
                                 jnp.asarray(i, jnp.int32))
        if i % 10 == 0:
            print(f"  step {i:3d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}")
    print(f"  final loss {float(m['loss']):.4f}")

    # greedy decode from a prompt (prefill + KV-cache steps)
    model_d = LMModel(cfg, opt=tfm.ApplyOptions(q_chunk=8, k_chunk=8, remat="none"))
    prompt = data.batch_at(0)["tokens"][:1, :8]
    logits, caches = jax.jit(
        lambda p, t: model_d.prefill(p, {"tokens": t}, 24))(params, prompt)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out = [int(tok[0, 0])]
    step = jax.jit(lambda p, t, c, i: model_d.decode_step(p, t, c, i))
    for i in range(8):
        lg, caches = step(params, tok, caches, jnp.asarray(8 + i, jnp.int32))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(int(tok[0, 0]))
    print(f"prompt ids: {np.asarray(prompt[0]).tolist()}")
    print(f"greedy continuation: {out}")


if __name__ == "__main__":
    main()
