"""Production training driver.

Trains any assigned architecture (or a reduced variant) with the SwitchAgg
gradient exchange, fault-tolerant loop (checkpoint/restart, straggler
monitor), deterministic data pipeline, and the mesh factorization of the
available devices.

CPU examples (the same code path a pod launch takes):

  # 100M-class model, tree exchange, checkpoints every 20 steps
  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \\
      --reduce --d-model 512 --layers 8 --steps 200 --batch 8 --seq 256 \\
      --mode tree --ckpt-dir /tmp/run1

  # multi-device tree exchange (8 fake devices, mesh 4x2)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.train --arch olmoe-1b-7b --reduce \\
      --mesh 4,2 --steps 50 --mode tree_compress
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.configs.reduced import reduced_config
from repro.core.collectives import GradAggMode
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.optim import AdamWConfig, adamw_init, make_lr_schedule
from repro.runtime.fault_tolerance import TrainLoop, TrainLoopConfig
from repro.train.compressed import build_compressed_train_step
from repro.train.step import TrainProfile, build_train_step

log = logging.getLogger("repro.launch.train")


def parse_mesh(spec: str | None):
    n = jax.device_count()
    if spec:
        dims = tuple(int(x) for x in spec.split(","))
    else:
        dims = (n, 1)
    names = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    assert np.prod(dims) == n, f"mesh {dims} != devices {n}"
    return make_mesh(dims, names)


def build_config(args):
    cfg = (reduced_config(args.arch) if args.reduce
           else configs.get_config(args.arch))
    over = {}
    if args.d_model:
        hd = max(16, args.d_model // max(cfg.n_heads, 1))
        over.update(d_model=args.d_model, head_dim=hd, d_ff=4 * args.d_model)
    if args.layers:
        per = len(cfg.pattern)
        groups = max(1, args.layers // per)
        over["n_layers"] = len(cfg.prefix) + groups * per
    if args.fp32:
        over["dtype"] = "float32"
    return dataclasses.replace(cfg, **over) if over else cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b", choices=configs.ARCH_IDS)
    ap.add_argument("--reduce", action="store_true",
                    help="use the reduced (CPU-scale) config")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default=None, help="e.g. 4,2 or 2,2,2")
    ap.add_argument("--mode", default="tree",
                    choices=[m.value for m in GradAggMode] + ["tree_compress"])
    ap.add_argument("--k-fraction", type=float, default=0.01)
    ap.add_argument("--fpe-capacity", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--q-chunk", type=int, default=128)
    ap.add_argument("--k-chunk", type=int, default=128)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = build_config(args)
    mesh = parse_mesh(args.mesh)
    log.info("config %s: %.1fM params (%.1fM active), mesh %s",
             cfg.name, cfg.param_count() / 1e6, cfg.active_param_count() / 1e6,
             dict(zip(mesh.axis_names, mesh.devices.shape)))

    mode = GradAggMode(args.mode)
    dp_axes = tuple(a for a in ("data", "pod") if a in mesh.axis_names)
    if mode != GradAggMode.TREE_COMPRESS:
        # exchange schedules order scarce-last in specs; step.py handles it
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    prof = TrainProfile(
        dp_axes=dp_axes, tp_axis="model",
        q_chunk=args.q_chunk, k_chunk=args.k_chunk,
        moe_token_chunk=max(64, args.batch * args.seq // 8),
        remat="none", mode=mode,
    )
    data = SyntheticLMData(cfg, DataConfig(seq_len=args.seq,
                                           global_batch=args.batch))
    opt_cfg = AdamWConfig(master_fp32=not args.fp32)
    lr_fn = make_lr_schedule(args.lr, min(20, args.steps // 10 + 1), args.steps)

    model = LMModel(cfg)
    params0 = model.init(jax.random.PRNGKey(0))
    batch0 = data.batch_at(0)

    if mode == GradAggMode.TREE_COMPRESS:
        step_fn, sh = build_compressed_train_step(
            cfg, mesh, prof, opt_cfg, lr_fn,
            batch_example=batch0, params_example=params0,
            k_fraction=args.k_fraction, fpe_capacity=args.fpe_capacity)
        params = jax.device_put(params0, sh["params"])
        opt = jax.jit(lambda p: adamw_init(p, opt_cfg),
                      out_shardings=sh["opt"])(params)
        res = jax.device_put(sh["res_example"], sh["residuals"])
        state = {"params": params, "opt": opt, "res": res}

        def loop_step(state, batch, i):
            p, o, r, m = step_fn(state["params"], state["opt"], state["res"],
                                 batch, jnp.asarray(i, jnp.int32))
            return {"params": p, "opt": o, "res": r}, m
    else:
        step_fn, sh, _ = build_train_step(
            cfg, mesh, prof, opt_cfg, lr_fn,
            batch_example=batch0, params_example=params0)
        params = jax.device_put(params0, sh["params"])
        opt = jax.jit(lambda p: adamw_init(p, opt_cfg),
                      out_shardings=sh["opt"])(params)
        state = {"params": params, "opt": opt}

        def loop_step(state, batch, i):
            p, o, m = step_fn(state["params"], state["opt"], batch,
                              jnp.asarray(i, jnp.int32))
            return {"params": p, "opt": o}, m

    def batch_fn(i):  # each batch lands split over the dp axes
        return jax.device_put(data.batch_at(i), sh["batch"])

    loop = TrainLoop(
        TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, log_every=args.log_every),
        loop_step, batch_fn, state,
    )
    t0 = time.time()
    final = loop.run()
    dt = time.time() - t0
    done = args.steps - loop.monitor._seen if False else len(loop.metrics_history)
    tok_s = done * args.batch * args.seq / max(dt, 1e-9)
    losses = [m["loss"] for m in loop.metrics_history]
    log.info("done: %d steps in %.1fs (%.0f tok/s); loss %.4f -> %.4f; "
             "stragglers=%d", done, dt, tok_s,
             losses[0] if losses else float("nan"),
             losses[-1] if losses else float("nan"),
             len(loop.monitor.events))
    return final, loop


if __name__ == "__main__":
    main()
