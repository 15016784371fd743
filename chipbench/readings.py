#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--fault half_batch --fault-seeds 4,5,6]

For every seed of ``--seeds`` it makes the cell's stream, runs one job of
the timed path at the cell's own size and compares it with the plain
reference (the lower reading: what sound runs give).  For every seed of
``--control-seeds`` it puts the reference computed in bfloat16 in the
program's place and compares that.  Then, with ``--fault``, it plants that
fault of ``faults.py`` under the timed path and runs one job for every
seed of ``--fault-seeds``.  The control and the fault give the upper
readings: what the comparison has to refuse.  Prints one line per seed
and, last, one JSON object with the largest sound reading and the
smallest reading of the control and of the fault, for each number.  The
benchmark's own runs never run this.
"""

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault")
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np

    (HERE / ".jax_cache").mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))

    from chipbench import compare as cmp
    from chipbench import faults, harness

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.chips)
    cell.plan = harness.build_plan(cell.config)
    gen = cell.module("data", cell.config["generator"])
    ref = cell.module("references", cell.config["reference"])
    driver = cell.module("drivers", cell.traffic["driver"])

    read = {"program": [], "control": [], "fault": []}
    runs = [("program", args.seeds), ("control", args.control_seeds)]
    if args.fault:
        runs.append(("fault", args.fault_seeds))
    for kind, seed_list in runs:
        if kind == "fault":
            faults.plant(args.fault, driver.ENTRY)
            jax.clear_caches()
        for seed in seed_list:
            t0 = time.perf_counter()
            keys, values = gen.generate(cell.config, seed,
                                        harness.prng_key(seed))
            if kind == "control":
                keys, values = np.asarray(keys), np.asarray(values)
                ids, tot = ref.control(keys, values)
                n = int(np.sum(keys != -1))
                numbers = cmp.compare(ids, tot, *ref.totals(keys, values),
                                      n, n)
            else:
                res = driver.prepare(cell, keys, values)()
                keys, values = np.asarray(keys), np.asarray(values)
                numbers = cmp.compare_job(res, *ref.totals(keys, values))
            read[kind].append(numbers)
            print(f"{kind} seed {seed}: {numbers} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)

    def least(xs):
        return {k: min(n[k] for n in xs) for k in cmp.LIMITS} if xs else None

    print(json.dumps({
        "workload": args.workload, "fault": args.fault,
        "seeds": {k: len(v) for k, v in read.items()},
        "lower": cmp.worst(read["program"]) if read["program"] else None,
        "upper_control": least(read["control"]),
        "upper_fault": least(read["fault"]),
        "limits": cmp.LIMITS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
