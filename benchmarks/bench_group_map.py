"""Group-map timing: the two branches of ``kvagg._group_reduce`` on the
backend this process runs (DESIGN.md §8, "Group maps split by platform").

``_group_reduce`` picks its branch by the platform it is lowered for: a
radix sort plus a binary-search group map on XLA:CPU
(``_group_reduce_searched``), one sort that carries the values elsewhere
(``_group_reduce_sorted``).  This bench jits each branch on its own, times
it over the same stream, checks that both give the same groups, and prints
one row per (n, branch), so the reason for the split can be re-measured:

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/bench_group_map.py
    PYTHONPATH=src python benchmarks/bench_group_map.py --sizes 98303 \
        --out benchmarks/out/BENCH_group_map.json

The default sizes are the stream cell's BPE stream (98,303) and 2^20.
Times are host wall-clock medians of ``--reps`` calls after a warm-up, on
whatever device JAX picked; a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:  # package import (benchmarks.run) or standalone CLI
    from benchmarks._util import write_bench_json
except ImportError:  # `python benchmarks/bench_*.py`: sys.path[0] is here
    from _util import write_bench_json

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "BENCH_group_map.json")
BRANCHES = ("searched", "sorted")


def _stream(n: int, seed: int = 0):
    """Keys over n/2 values with a quarter EMPTY padding, small int values."""
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    keys = r.integers(0, max(n // 2, 1), size=n).astype(np.int32)
    keys[r.random(n) < 0.25] = -1
    vals = r.integers(1, 9, size=n).astype(np.float32)
    return jnp.asarray(keys), jnp.asarray(vals)


def run_size(n: int, *, op: str = "sum", reps: int = 7) -> list[dict]:
    """One row per branch at stream length ``n``; raises if the branches
    disagree on sorted keys, first occurrences or first-position totals."""
    import jax

    from repro.core import kvagg

    keys, vals = _stream(n)
    rows, outs = [], {}
    for branch in BRANCHES:
        fn = jax.jit(functools.partial(
            getattr(kvagg, f"_group_reduce_{branch}"), op=op))
        outs[branch] = [np.asarray(x)
                        for x in jax.block_until_ready(fn(keys, vals))]
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(keys, vals))
            ts.append(time.perf_counter() - t)
        rows.append({"n": n, "op": op, "branch": branch,
                     "backend": jax.default_backend(),
                     "ms": statistics.median(ts) * 1e3})
    (ks_a, first_a, comb_a), (ks_b, first_b, comb_b) = (
        outs[b] for b in BRANCHES)
    if not (np.array_equal(ks_a, ks_b) and np.array_equal(first_a, first_b)
            and np.array_equal(comb_a[first_a], comb_b[first_b])):
        raise AssertionError(f"group-map branches disagree at n={n}")
    return rows


def main(argv=None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="98303,1048576")
    ap.add_argument("--op", default="sum")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--out", default=None,
                    help=f"write BENCH json here (e.g. {DEFAULT_OUT})")
    args = ap.parse_args(argv)
    rows = []
    for n in (int(s) for s in args.sizes.split(",")):
        rows += run_size(n, op=args.op, reps=args.reps)
    for r in rows:
        print(f"{r['backend']:>4} n={r['n']:>8} {r['branch']:>8} "
              f"{r['ms']:.3f} ms")
    if args.out:
        write_bench_json(rows, args.out, bench="group_map")
    return rows


if __name__ == "__main__":
    main()
