#!/usr/bin/env python3
"""Bring-up check: the system's main paths on a TPU, each against a plain
reference.

    python chip_smoke.py              # one chip: node + cascade, simulator,
                                      # gradient-exchange training
    python chip_smoke.py --chips 4    # four chips: data-parallel training
                                      # with the gradient exchange, only

Every phase runs through the entry points a user calls
(``repro.core.plan`` -> ``run_cascade`` / ``run_cascade_stream``,
``repro.net.simulate``, ``repro.launch.train.main``) and prints its sizes,
what it compared, the largest difference and its compile seconds.  Any
failed comparison or exception ends the run with a non-zero exit.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script refuses to run (non-zero exit, no result line) where JAX finds
no TPU, and where the repository's ``src/`` is not beside it.  It never
falls back to the CPU.  Data comes from ``--seed``; training checkpoints go
to a fresh directory under ``--out``, which is removed after each run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
SRC = HERE / "src"


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the smoke runs at; ``TINY`` is the CPU rehearsal."""

    # aggregation node and cascade: a word-count stream, Zipf keys whose
    # variety (2^20) sits far above the table (65,536 pairs per node)
    records: int = 1 << 24
    key_variety: int = 1 << 20
    zipf_skew: float = 1.1
    capacity: int = 1 << 16
    ways: int = 4
    block_n: int = 512
    # the serial scan reference runs on a prefix of the stream
    scan_records: int = 1 << 20
    # fast-path packets: the most records with n * capacity < 2**31 - 1
    packet: int = (2**31 - 2) // (1 << 16)
    # the 3-level fat tree the cascade is placed on
    ft_pods: int = 4
    ft_tors: int = 4
    ft_hosts: int = 16
    # simulator: the fat16_tor geometry of benchmarks/bench_sim.py
    sim_pods: int = 16
    sim_tors: int = 8
    sim_hosts: int = 16
    sim_pairs_per_host: int = 64
    sim_variety: int = 2048
    sim_rpp: int = 4
    sim_table_pairs: int = 2048
    # training: mamba2-780m at its published widths, depth cut to 8
    train_args: tuple[str, ...] = ("--arch", "mamba2-780m", "--layers", "8",
                                   "--seq", "2048")
    batch_per_chip: int = 1
    train_steps: int = 5
    multichip_steps: int = 3


TINY = Sizes(records=1 << 13, key_variety=1 << 11, capacity=256,
             block_n=128, scan_records=1 << 11, packet=1 << 10,
             ft_pods=2, ft_tors=2, ft_hosts=2,
             sim_pods=2, sim_tors=2, sim_hosts=4, sim_pairs_per_host=16,
             sim_variety=64, sim_table_pairs=32,
             train_args=("--arch", "mamba2-780m", "--reduce", "--seq", "32"),
             batch_per_chip=2, train_steps=2, multichip_steps=2)

#: step-0 loss and grad norm of the lossless tree_compress exchange
#: (k-fraction 1.0) against flat: same data, same init, f32 accumulation
STEP0_RTOL = 1e-3
#: later losses differ only by the order of bf16/f32 reductions feeding
#: AdamW, which moves every parameter by about the learning rate
LOSS_RTOL = 2e-3


def group_by(keys: np.ndarray, n_keys: int) -> np.ndarray:
    """The plain reference: word-count totals per key (values are 1)."""
    return np.bincount(keys, minlength=n_keys).astype(np.float64)


def compare_totals(phase: str, what: str, got_k, got_v, want: np.ndarray):
    """Packed (keys, totals) against the reference; exact for counts."""
    got_k = np.asarray(got_k)
    got_v = np.asarray(got_v, np.float64)
    real = got_k != -1
    gk, gv = got_k[real], got_v[real]
    check(len(np.unique(gk)) == len(gk), f"{phase}: {what} repeats keys")
    dense = np.zeros_like(want)
    dense[gk] = gv
    err = float(np.max(np.abs(dense - want)))
    nz = int(np.count_nonzero(want))
    log(phase, f"{what}: {len(gk)} keys vs {nz} in the reference, "
               f"max |total - reference| = {err}")
    check(len(gk) == nz and err == 0.0, f"{phase}: {what} differs from the "
          f"host group-by ({len(gk)} vs {nz} keys, max error {err})")


def timed_compile(fn, *args, **static):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    return compiled, time.perf_counter() - t0


def node_phase(sz: Sizes, seed: int, *, interpret: bool) -> None:
    """plan() -> fat-tree placement -> 3-level cascade, Pallas and fast path."""
    import jax
    import jax.numpy as jnp

    from repro.core import aggops, dataplane, kvagg, plan, planner
    from repro.core import reduction_model as rm
    from repro.kernels.kv_aggregate import fpe_aggregate_pallas

    phase = "node"
    keys_np = rm.zipf_keys(sz.records, sz.key_variety, skew=sz.zipf_skew,
                           seed=seed).astype(np.int32)
    want = group_by(keys_np, sz.key_variety)
    keys = jnp.asarray(keys_np)
    ones = jnp.ones((sz.records,), jnp.float32)
    log(phase, f"stream: {sz.records} records, Zipf({sz.zipf_skew}) over "
               f"{sz.key_variety} keys ({int(np.count_nonzero(want))} "
               f"present), values 1.0 (float32)")

    ft = planner.FatTreeTopology(pods=sz.ft_pods, tors_per_pod=sz.ft_tors,
                                 hosts_per_tor=sz.ft_hosts,
                                 table_pairs=sz.capacity)
    for op in ("sum", "mean"):
        req = planner.LaunchRequest(
            job_id=1, n_workers=ft.n_hosts,
            expected_pairs=sz.records // ft.n_hosts,
            key_variety=sz.key_variety, op=op)
        cplan = dataplane.plan_from_placement(
            plan(req, ft, policy="full").configure, op=op, ways=sz.ways)
        check(len(cplan.levels) == 3 and all(
            l.enabled and l.capacity == sz.capacity for l in cplan.levels),
            f"{phase}: placement gave {cplan}")
        log(phase, f"{op}: {cplan.describe()}, {sz.ways} ways, placed on a "
                   f"{sz.ft_pods}x{sz.ft_tors}x{sz.ft_hosts} fat tree")

        # paper-faithful cascade: the Pallas FPE at every level
        cascade, secs = timed_compile(
            dataplane.run_cascade, keys, ones, cplan, backend="pallas",
            block_n=sz.block_n, interpret=interpret, exact_stream=True,
            finalize=False)
        t0 = time.perf_counter()
        res = jax.block_until_ready(cascade(keys, ones))
        log(phase, f"{op}: pallas cascade compiled in {secs:.1f}s, ran in "
                   f"{time.perf_counter() - t0:.1f}s; records per level in "
                   f"{np.asarray(res.level_in).tolist()} out "
                   f"{np.asarray(res.level_out).tolist()}")
        lanes = np.asarray(res.values).reshape(res.keys.shape[0], -1)
        for lane in range(lanes.shape[1]):  # sum; mean's (sum, count)
            compare_totals(phase, f"{op} pallas cascade lane {lane}",
                           res.keys, lanes[:, lane], want)

        # the fast path, streamed in packets below its int32 limit
        t0 = time.perf_counter()
        stream = dataplane.run_cascade_stream(
            ((keys_np[i:i + sz.packet], np.ones(
                min(sz.packet, sz.records - i), np.float32))
             for i in range(0, sz.records, sz.packet)),
            cplan, batch_pad=sz.packet, exact_stream=False, finalize=False)
        lanes = np.asarray(stream.values).reshape(stream.keys.shape[0], -1)
        log(phase, f"{op}: fast-path stream of {sz.packet}-record packets "
                   f"ran in {time.perf_counter() - t0:.1f}s (compile "
                   f"included)")
        for lane in range(lanes.shape[1]):
            compare_totals(phase, f"{op} fast-path stream lane {lane}",
                           stream.keys, lanes[:, lane], want)

        # the kernel against the serial scan, element for element
        pk = keys[:sz.scan_records]
        pv = aggops.get(op).prepare_values(ones[:sz.scan_records])
        kernel, secs = timed_compile(
            fpe_aggregate_pallas, pk, pv, capacity=sz.capacity,
            ways=sz.ways, op=op, block_n=sz.block_n, interpret=interpret)
        got = jax.device_get(kernel(pk, pv))
        ref = jax.device_get(kvagg.fpe_aggregate(
            pk, pv, capacity=sz.capacity, ways=sz.ways, op=op,
            exact_stream=True))
        names = ("table keys", "table values", "eviction keys",
                 "eviction values")
        same = [np.array_equal(a, b) for a, b in zip(got, ref)]
        n_ev = int(np.sum(ref[2] != -1))
        log(phase, f"{op}: pallas FPE (compiled in {secs:.1f}s) vs the "
                   f"scan on the first {sz.scan_records} records: "
                   + ", ".join(f"{n} {'identical' if s else 'DIFFER'}"
                               for n, s in zip(names, same))
                   + f" ({n_ev} evictions)")
        check(all(same), f"{phase}: {op} kernel differs from the scan")


def sim_phase(sz: Sizes, seed: int) -> None:
    """repro.net.simulate on the vectorized engine vs the host group-by."""
    from repro.core import planner
    from repro.core import reduction_model as rm
    from repro.net import simulate
    from repro.net import sim as netsim

    phase = "sim"
    ft = planner.FatTreeTopology(pods=sz.sim_pods, tors_per_pod=sz.sim_tors,
                                 hosts_per_tor=sz.sim_hosts,
                                 oversubscription=4.0,
                                 table_pairs=sz.sim_table_pairs)
    n = ft.n_hosts * sz.sim_pairs_per_host
    keys = rm.zipf_keys(n, sz.sim_variety, skew=0.99,
                        seed=seed).astype(np.int32)
    vals = np.ones((n,), np.float32)
    placement = planner.place_aggregation_tree(
        ft, per_host_pairs=sz.sim_pairs_per_host,
        key_variety=sz.sim_variety, policy="tor_only")
    cfg = netsim.NetConfig(records_per_packet=sz.sim_rpp, exact_stream=True,
                           engine="vectorized")
    t0 = time.perf_counter()
    res = simulate(ft, keys, vals, placement=placement, cfg=cfg)
    log(phase, f"{sz.sim_pods} pods, {ft.n_hosts} mappers x "
               f"{sz.sim_pairs_per_host} records, {sz.sim_rpp} records per "
               f"packet, ToR tables of {sz.sim_table_pairs} pairs: ran in "
               f"{time.perf_counter() - t0:.1f}s (compile included), "
               f"{sum(l['records_in'] for l in res.per_level)} switch steps")
    table = res.delivered_table()
    compare_totals(phase, "delivered table",
                   np.fromiter(table, np.int64, len(table)),
                   np.fromiter(table.values(), np.float64, len(table)),
                   group_by(keys, sz.sim_variety))


def _train(sz: Sizes, out: pathlib.Path, n_chips: int, mode: str,
           steps: int, k_fraction: float | None = None):
    """One ``repro.launch.train.main`` run in a fresh checkpoint dir."""
    from repro.launch import train

    ckpt = pathlib.Path(tempfile.mkdtemp(prefix=f"ckpt_{mode}_", dir=out))
    argv = [*sz.train_args, "--batch", str(sz.batch_per_chip * n_chips),
            "--steps", str(steps), "--mesh", f"{n_chips},1", "--mode", mode,
            "--ckpt-dir", str(ckpt), "--log-every", str(steps)]
    if k_fraction is not None:
        argv += ["--k-fraction", str(k_fraction)]
    try:
        _, loop = train.main(argv)
        hist = loop.metrics_history
        batches = [loop.batch_fn(i) for i in range(steps)]
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    loss = np.array([m["loss"] for m in hist])
    gnorm = np.array([m["grad_norm"] for m in hist])
    label = mode + (f" k={k_fraction}" if k_fraction is not None else "")
    log("train", f"{label}: {len(hist)} steps, first step "
                 f"{hist[0]['step_time_s']:.1f}s (compile included), loss "
                 f"{np.array2string(loss, precision=5)}, grad norm "
                 f"{np.array2string(gnorm, precision=5)}")
    check(len(hist) == steps and np.all(np.isfinite(loss))
          and np.all(np.isfinite(gnorm)), f"train: {label} not finite")
    return loss, gnorm, batches


def _rel(a, b) -> float:
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def train_phase(sz: Sizes, out: pathlib.Path, n_chips: int) -> None:
    """flat vs lossless tree_compress on one chip, through train.main."""
    log("train", f"{' '.join(sz.train_args)}, batch "
                 f"{sz.batch_per_chip * n_chips} on {n_chips} chip(s)")
    f_loss, f_norm, _ = _train(sz, out, n_chips, "flat", sz.train_steps)
    c_loss, c_norm, _ = _train(sz, out, n_chips, "tree_compress",
                               sz.train_steps, k_fraction=1.0)
    dl, dn = _rel(c_loss[:1], f_loss[:1]), _rel(c_norm[:1], f_norm[:1])
    log("train", f"step 0, tree_compress k=1.0 vs flat: loss rel diff {dl:.3g}"
                 f", grad norm rel diff {dn:.3g} (limit {STEP0_RTOL})")
    check(dl <= STEP0_RTOL and dn <= STEP0_RTOL,
          "train: lossless tree_compress disagrees with flat at step 0")


def multichip_phase(sz: Sizes, out: pathlib.Path, n_chips: int) -> None:
    """Data-parallel exchange on a (data=n, model=1) mesh: every mode from
    one init against flat, plus the compressing default."""
    steps = sz.multichip_steps
    log("train", f"{' '.join(sz.train_args)}, batch "
                 f"{sz.batch_per_chip * n_chips} on a (data={n_chips}, "
                 f"model=1) mesh, {steps} steps per mode")
    f_loss, f_norm, batches = _train(sz, out, n_chips, "flat", steps)
    for i, batch in enumerate(batches):
        for name, arr in batch.items():
            devs = {s.device for s in arr.addressable_shards}
            rows = {s.data.shape[0] for s in arr.addressable_shards}
            check(len(devs) == n_chips and rows == {sz.batch_per_chip},
                  f"train: batch {i} {name} sits on {len(devs)} devices "
                  f"in shards of {rows} rows")
    log("train", f"every batch is split over {n_chips} distinct devices, "
                 f"{sz.batch_per_chip} row(s) each")
    for mode, kf in (("tree", None), ("tree_compress", 1.0)):
        loss, norm, _ = _train(sz, out, n_chips, mode, steps, k_fraction=kf)
        dl, dn = _rel(loss, f_loss), _rel(norm[:1], f_norm[:1])
        log("train", f"{mode} vs flat: loss rel diff {dl:.3g} (limit "
                     f"{LOSS_RTOL}), step-0 grad norm rel diff {dn:.3g} "
                     f"(limit {STEP0_RTOL})")
        check(dl <= LOSS_RTOL and dn <= STEP0_RTOL,
              f"train: {mode} disagrees with flat")
    _train(sz, out, n_chips, "tree_compress", steps)


def tpu_devices(n_chips: int):
    """The TPU devices, or exit: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); refusing to run")
    if len(devices) != n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} but JAX reports "
                 f"{len(devices)} TPU device(s)")
    return devices


def run(args, n_chips: int, *, interpret: bool) -> None:
    """Every phase of one invocation; raises on the first failure."""
    sz = TINY if args.tiny else Sizes()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip_phase(sz, out, n_chips)
    else:
        node_phase(sz, args.seed, interpret=interpret)
        sim_phase(sz, args.seed)
        train_phase(sz, out, n_chips)
    log("smoke", f"all phases passed in {time.perf_counter() - t0:.1f}s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel training exchange")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (still needs the TPU)")
    ap.add_argument("--out", default=str(HERE / ".chip_smoke"),
                    help="scratch directory for training checkpoints")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repository sources at {SRC}")
    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    log("smoke", f"{len(devices)} x {devices[0].device_kind}, compile cache "
                 f"{enable_compile_cache()}")
    run(args, len(devices), interpret=False)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
