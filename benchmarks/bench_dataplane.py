"""Cascade dataplane benchmark: capacity × levels × op sweep (DESIGN.md §6).

For each configuration a synthetic KV stream runs through a plan-driven
multi-level cascade (``core.dataplane.run_cascade``) and we record the
paper's key metric — per-level and end-to-end reduction ratio — plus wall
time, into a stable JSON (``BENCH_dataplane.json``) that CI regenerates
every run so the perf trajectory is tracked from this PR onward.

    PYTHONPATH=src python benchmarks/bench_dataplane.py
    PYTHONPATH=src python benchmarks/bench_dataplane.py --smoke \
        --out benchmarks/out/BENCH_dataplane.json

``--smoke`` runs the smallest config per op on the Pallas backend in
interpret mode (CPU) — the CI job — and cross-checks the cascade against
the exact grouped combine so a semantics regression fails the bench, not
just the unit suite.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:  # package import (benchmarks.run) or standalone CLI
    from benchmarks._util import write_bench_json
except ImportError:  # `python benchmarks/bench_*.py`: sys.path[0] is here
    from _util import write_bench_json

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "BENCH_dataplane.json")


def run_config(op: str, n_levels: int, capacity: int, *, n: int = 8192,
               variety: int = 1024, dist: str = "zipf", backend: str = "jnp",
               ways: int = 4, block_n: int = 256, reps: int = 3,
               check: bool = False) -> dict:
    """One cell: ``n_levels`` nodes of ``capacity`` pairs each, one op."""
    import jax
    import jax.numpy as jnp

    from repro.core import dataplane, kvagg
    from repro.core import reduction_model as rm

    plan = dataplane.CascadePlan(
        op=op, levels=tuple(dataplane.LevelSpec(capacity=capacity, ways=ways)
                            for _ in range(n_levels)))
    gen = rm.uniform_keys if dist == "uniform" else rm.zipf_keys
    keys = jnp.asarray(gen(n, variety, seed=0).astype(np.int32))
    vals = jnp.asarray(np.random.default_rng(0).standard_normal(n)
                       .astype(np.float32))

    interpret = backend == "pallas"  # a CPU bench: Pallas interpreted

    def once():
        return dataplane.run_cascade(keys, vals, plan, backend=backend,
                                     block_n=block_n, interpret=interpret)

    res = once()  # warmup / compile
    res.keys.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        res = once()
        res.keys.block_until_ready()
    wall_us = (time.perf_counter() - t0) / reps * 1e6

    if check:  # smoke-mode semantics cross-check vs the exact combine
        from repro.core import aggops

        aggop = aggops.get(op)
        exact = kvagg.sorted_combine(keys, aggop.prepare_values(vals), op=op)
        ek = np.asarray(exact.unique_keys)
        ev = np.asarray(aggop.finalize_values(exact.combined_values))
        gk, gv = np.asarray(res.keys), np.asarray(res.values)
        nu = int(exact.n_unique)
        got = dict(zip(gk[gk != -1].tolist(), gv[: len(gk)][gk != -1].tolist()))
        want = dict(zip(ek[:nu].tolist(), ev[:nu].tolist()))
        assert got.keys() == want.keys(), f"{op}: key set mismatch"
        for kk in want:
            np.testing.assert_allclose(got[kk], want[kk], rtol=1e-4,
                                       atol=1e-5, err_msg=f"op={op} key={kk}")

    tele = dataplane.telemetry(res, plan)
    preds = dataplane.predicted_level_reductions(plan, n, variety)
    return {
        "op": op,
        "levels": n_levels,
        "capacity_per_node": capacity,
        "ways": ways,
        "n": n,
        "key_variety": variety,
        "dist": dist,
        "backend": backend,
        "reduction_per_level": [l["reduction"] for l in tele["levels"]],
        "evictions_per_level": [l["evictions"] for l in tele["levels"]],
        "predicted_per_level": [round(p, 4) for p in preds],
        "end_to_end_reduction": tele["end_to_end_reduction"],
        "wall_us": round(wall_us, 1),
    }


def sweep(*, ops, capacities, levels, n: int, variety: int, dist: str,
          backend: str, reps: int, check: bool = False) -> list[dict]:
    rows = []
    for op in ops:
        for nl in levels:
            for cap in capacities:
                rows.append(run_config(op, nl, cap, n=n, variety=variety,
                                       dist=dist, backend=backend, reps=reps,
                                       check=check))
    rows.sort(key=lambda r: (r["op"], r["levels"], r["capacity_per_node"]))
    return rows


def smoke_rows() -> list[dict]:
    """Smallest config per registered op, Pallas FPE in interpret mode."""
    from repro.core import aggops

    return sweep(ops=aggops.names(), capacities=[16], levels=[2], n=256,
                 variety=64, dist="zipf", backend="pallas", reps=1,
                 check=True)


def write_out(rows: list[dict], out_path: str) -> None:
    write_bench_json(rows, out_path, bench="dataplane")


def print_rows(rows: list[dict]) -> None:
    hdr = (f"{'op':<10} {'lvls':>4} {'cap':>6} {'backend':<7} "
           f"{'R end2end':>9} {'R/level':<23} {'us':>9}")
    print(hdr)
    for r in rows:
        per = "/".join(f"{x:.2f}" for x in r["reduction_per_level"])
        print(f"{r['op']:<10} {r['levels']:>4} {r['capacity_per_node']:>6} "
              f"{r['backend']:<7} {r['end_to_end_reduction']:>9.4f} "
              f"{per:<23} {r['wall_us']:>9.0f}")


def main() -> None:
    from repro.core import aggops

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", default=",".join(aggops.names()))
    ap.add_argument("--capacities", default="32,128,512")
    ap.add_argument("--levels", default="1,2,4")
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--variety", type=int, default=1024)
    ap.add_argument("--dist", choices=["uniform", "zipf"], default="zipf")
    ap.add_argument("--backend", choices=["jnp", "pallas"], default="jnp")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="smallest config per op, pallas interpret + "
                         "exactness cross-check (the CI job)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()

    if args.smoke:
        rows = smoke_rows()
    else:
        rows = sweep(ops=args.ops.split(","),
                     capacities=[int(c) for c in args.capacities.split(",")],
                     levels=[int(l) for l in args.levels.split(",")],
                     n=args.n, variety=args.variety, dist=args.dist,
                     backend=args.backend, reps=args.reps)
    print_rows(rows)
    write_out(rows, args.out)


if __name__ == "__main__":
    main()
