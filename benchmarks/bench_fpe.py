"""FPE throughput benchmark: pairs/sec, scan oracle vs batched-block fast
path (DESIGN.md §8).

The FPE is the hot loop under every cascade, the packet simulator, and
``train/compressed`` — this bench is its first throughput trajectory.
Each cell runs one FPE call over a synthetic stream twice: the
paper-faithful sequential scan (``exact_stream=True``) and the batched
fast path (``exact_stream=False``; pre-combine + closed-form vectorized
bucket update), and records pairs/sec for both plus the speedup, into a
stable JSON (``BENCH_fpe.json``) CI regenerates every run.

    PYTHONPATH=src python benchmarks/bench_fpe.py
    PYTHONPATH=src python benchmarks/bench_fpe.py --smoke \
        --out benchmarks/out/BENCH_fpe.json

``--smoke`` runs one small config per registered op on both backends
(Pallas in interpret mode — the CI job) and cross-checks that both modes'
(flush + evictions) grouped by key equal the exact input combine, so a
fast-path semantics regression fails the bench, not just the unit suite.

The headline acceptance cell (run by default, asserted with ``--check``):
a 100k-pair Zipf stream on the jnp backend must clear >= 5x pairs/sec for
the fast path over the scan oracle.
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
                           "BENCH_fpe.json")
EMPTY = -1


def _stream(n: int, variety: int, dist: str, seed: int = 0):
    import jax.numpy as jnp

    from repro.core import reduction_model as rm

    gen = rm.uniform_keys if dist == "uniform" else rm.zipf_keys
    keys = jnp.asarray(gen(n, variety, seed=seed).astype(np.int32))
    vals = jnp.asarray(np.random.default_rng(seed)
                       .standard_normal(n).astype(np.float32))
    return keys, vals


def _fpe_call(backend: str, interpret: bool):
    """One (keys, values, op, geometry, exact_stream) -> FPE 4-tuple."""
    if backend == "pallas":
        from repro.kernels.kv_aggregate import fpe_aggregate_pallas

        def call(keys, vals, *, op, capacity, ways, exact_stream):
            return fpe_aggregate_pallas(
                keys, vals, op=op, capacity=capacity, ways=ways,
                exact_stream=exact_stream, interpret=interpret)
    elif backend == "jnp":
        from repro.core import kvagg

        def call(keys, vals, *, op, capacity, ways, exact_stream):
            return tuple(kvagg.fpe_aggregate(
                keys, vals, op=op, capacity=capacity, ways=ways,
                exact_stream=exact_stream))
    else:
        raise ValueError(f"unknown backend: {backend!r}")
    return call


def _time_mode(call, keys, vals, *, op, capacity, ways, exact_stream,
               reps: int) -> float:
    """Best-of-reps wall time — min is the standard noise-robust estimator
    for a deterministic computation on a shared machine."""
    import jax

    def once():
        return jax.block_until_ready(call(
            keys, vals, op=op, capacity=capacity, ways=ways,
            exact_stream=exact_stream))

    once()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        once()
        best = min(best, time.perf_counter() - t0)
    return best


def _conservation_check(call, keys, vals, *, op, capacity, ways,
                        exact_stream) -> None:
    """(flush + evictions) grouped by key must equal the exact combine."""
    import jax.numpy as jnp

    from repro.core import aggops, kvagg

    aggop = aggops.get(op)
    carried = aggop.prepare_values(vals)
    tk, tv, ek, ev = call(keys, carried, op=op, capacity=capacity,
                          ways=ways, exact_stream=exact_stream)
    got = kvagg.sorted_combine(jnp.concatenate([tk, ek]),
                               jnp.concatenate([tv, ev]), op=op)
    want = kvagg.sorted_combine(keys, carried, op=op)
    nu = int(want.n_unique)
    mode = "fast" if not exact_stream else "scan"
    assert int(got.n_unique) == nu, \
        f"{op}/{mode}: {int(got.n_unique)} unique keys, expected {nu}"
    np.testing.assert_array_equal(
        np.asarray(got.unique_keys)[:nu], np.asarray(want.unique_keys)[:nu])
    np.testing.assert_allclose(
        np.asarray(got.combined_values)[:nu],
        np.asarray(want.combined_values)[:nu], rtol=1e-4, atol=1e-5,
        err_msg=f"op={op} mode={mode} conservation broken")


def run_config(op: str, *, n: int = 100_000, variety: int = 4096,
               capacity: int = 1024, ways: int = 4, dist: str = "zipf",
               backend: str = "jnp", interpret: bool = False,
               reps: int = 3, scan_reps: int = 1,
               check: bool = False) -> dict:
    """One cell: time the scan oracle and the fast path on one stream."""
    from repro.core import aggops

    keys, vals = _stream(n, variety, dist)
    carried = aggops.get(op).prepare_values(vals)
    call = _fpe_call(backend, interpret)

    fast_s = _time_mode(call, keys, carried, op=op, capacity=capacity,
                        ways=ways, exact_stream=False, reps=reps)
    scan_s = _time_mode(call, keys, carried, op=op, capacity=capacity,
                        ways=ways, exact_stream=True, reps=scan_reps)
    if check:
        for exact in (True, False):
            _conservation_check(call, keys, vals, op=op, capacity=capacity,
                                ways=ways, exact_stream=exact)
    return {
        "op": op,
        "n": n,
        "key_variety": variety,
        "capacity": capacity,
        "ways": ways,
        "dist": dist,
        "backend": backend,
        "scan_us": round(scan_s * 1e6, 1),
        "fast_us": round(fast_s * 1e6, 1),
        "scan_pairs_per_s": round(n / scan_s, 1),
        "fast_pairs_per_s": round(n / fast_s, 1),
        "speedup": round(scan_s / fast_s, 2),
    }


def sweep(*, ops, lengths, ways_list, backends, variety: int,
          capacity: int, dist: str, reps: int, interpret: bool = False,
          check: bool = False) -> list[dict]:
    rows = []
    for backend in backends:
        for op in ops:
            for n in lengths:
                for ways in ways_list:
                    rows.append(run_config(
                        op, n=n, variety=variety, capacity=capacity,
                        ways=ways, dist=dist, backend=backend,
                        interpret=interpret, reps=reps, check=check))
    rows.sort(key=lambda r: (r["backend"], r["op"], r["n"], r["ways"]))
    return rows


def smoke_rows() -> list[dict]:
    """One small config per registered op, both backends; Pallas runs in
    interpret mode; every cell cross-checks conservation in both modes."""
    from repro.core import aggops

    rows = sweep(ops=aggops.names(), lengths=[2048], ways_list=[4],
                 backends=["jnp"], variety=256, capacity=128, dist="zipf",
                 reps=1, check=True)
    rows += sweep(ops=aggops.names(), lengths=[512], ways_list=[4],
                  backends=["pallas"], variety=64, capacity=32, dist="zipf",
                  reps=1, interpret=True, check=True)
    return rows


def headline_row(*, reps: int = 3, check: bool = True) -> dict:
    """THE acceptance cell: 100k-pair Zipf, jnp backend, sum."""
    return run_config("sum", n=100_000, variety=4096, capacity=1024,
                      ways=4, dist="zipf", backend="jnp", reps=reps,
                      scan_reps=2, check=check)


def write_out(rows: list[dict], out_path: str) -> None:
    write_bench_json(rows, out_path, bench="fpe")


def print_rows(rows: list[dict]) -> None:
    hdr = (f"{'op':<10} {'backend':<7} {'n':>7} {'ways':>4} "
           f"{'scan pairs/s':>13} {'fast pairs/s':>13} {'speedup':>8}")
    print(hdr)
    for r in rows:
        print(f"{r['op']:<10} {r['backend']:<7} {r['n']:>7} {r['ways']:>4} "
              f"{r['scan_pairs_per_s']:>13.0f} {r['fast_pairs_per_s']:>13.0f} "
              f"{r['speedup']:>7.1f}x")


def main() -> None:
    from repro.core import aggops

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", default=",".join(aggops.names()))
    ap.add_argument("--lengths", default="8192,100000")
    ap.add_argument("--ways", default="4,16")
    ap.add_argument("--backends", default="jnp")
    ap.add_argument("--variety", type=int, default=4096)
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--dist", choices=["uniform", "zipf"], default="zipf")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="small config per op + pallas interpret, with the "
                         "both-modes conservation cross-check (the CI job)")
    ap.add_argument("--check", action="store_true",
                    help="assert the headline 100k Zipf cell clears 5x")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()

    if args.smoke:
        rows = smoke_rows()
    else:
        rows = sweep(ops=args.ops.split(","),
                     lengths=[int(x) for x in args.lengths.split(",")],
                     ways_list=[int(x) for x in args.ways.split(",")],
                     backends=args.backends.split(","),
                     variety=args.variety, capacity=args.capacity,
                     dist=args.dist, reps=args.reps)
        hl = headline_row()
        rows.append(hl)
        print(f"headline: 100k Zipf jnp sum -> {hl['speedup']}x "
              f"({hl['fast_pairs_per_s']:.0f} pairs/s fast vs "
              f"{hl['scan_pairs_per_s']:.0f} scan)")
        if args.check:
            assert hl["speedup"] >= 5.0, \
                f"fast path speedup {hl['speedup']}x < 5x acceptance bar"
    print_rows(rows)
    write_out(rows, args.out)


if __name__ == "__main__":
    main()
