#!/usr/bin/env python3
"""Record the small chip traces the trace-reduction tests read.

    python3 chipbench/tests/record_trace.py --out <dir>

Runs each cell of ``BENCHMARK.json`` through the harness at a small size
(a 2^14-record word stream, 1,024 orders, 1,024-pair tables,
1,024-record batches, a window of 0.05 s) with
``--trace 1`` on the chip, copies each run's ``.xplane.pb`` to
``<out>/<cell>.xplane.pb`` and a gzipped copy beside it, and prints what
the trace holds: its planes and lines, a few device operations, and the
result line.  Copy the ``.gz`` files into ``chipbench/tests/data/`` to
refresh the fixtures.
"""

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]

SMALL_CONFIG = {"records": 1 << 14, "expected_records": 1 << 14,
                "orders_per_scale": 1024}
SMALL_TREE = {"pods": 2, "tors_per_pod": 2, "hosts_per_tor": 2,
              "table_pairs": 1024}
SMALL_TRAFFIC = {"batch_records": 1024}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True, type=pathlib.Path)
    ap.add_argument("--seconds", type=float, default=0.05)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from jax.profiler import ProfileData

    from chipbench import harness

    args.out.mkdir(parents=True, exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        cell.config.update({k: v for k, v in SMALL_CONFIG.items()
                            if k in cell.config}, fat_tree=SMALL_TREE)
        cell.traffic.update({k: v for k, v in SMALL_TRAFFIC.items()
                             if k in cell.traffic})
        path = args.out / f"{w['name']}.xplane.pb"
        result = harness.run_cell(cell, 7, args.seconds, True,
                                  t_start=T_START, keep_trace=path)
        with open(path, "rb") as src, gzip.open(f"{path}.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        print(f"== {w['name']}: {path.stat().st_size} bytes")
        for plane in ProfileData.from_file(str(path)).planes:
            lines = {ln.name: sum(1 for _ in ln.events) for ln in plane.lines}
            print(f"plane {plane.name!r}: {lines}")
            for ln in plane.lines:
                if ln.name != "XLA Ops":
                    continue
                seen = set()
                for e in ln.events:
                    text = " ".join(str(v) for _, v in e.stats)
                    if e.name in seen or not ("fpe" in text.lower()
                                              or len(seen) < 3):
                        continue
                    seen.add(e.name)
                    print(f"   op {e.name!r} stats "
                          f"{ {k: str(v)[:300] for k, v in e.stats} }")
        print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
