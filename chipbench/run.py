#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program under ``src/``.  The last line of standard
output is one JSON object: ``correct``, ``attempted`` and ``failed`` (jobs
of the window), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: every number compared, with its limit.
The same numbers are the last lines of standard error.

Exits non-zero with no result line where JAX finds no TPU or fewer chips
than the cell asks for, or where the checkout lacks the program.  JAX's
persistent compilation cache is kept in ``chipbench/.jax_cache`` inside
the checkout, so only a checkout's first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
