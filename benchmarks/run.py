"""Benchmark harness orchestrator.

Runs every paper-table/figure reproduction plus the TPU-domain collective
accounting, prints a ``name,us_per_call,derived`` CSV, and writes the full
JSON to benchmarks/out/results.json (EXPERIMENTS.md §Paper-validation reads
from it).

    PYTHONPATH=src python -m benchmarks.run

``--smoke`` is THE consolidated CI entry: every bench suite's smoke
configuration (multijob, dataplane, FPE, JCT, placement) runs in one
process and every ``BENCH_*.json`` lands in one output directory for a
single artifact upload — replacing the per-bench copy-pasted CI steps.
Each suite keeps its own cross-checks (conservation, exactly-once,
placement acceptance), so a semantics regression still fails the step.
``--ci`` additionally keeps stdout terse (one line per suite).

    PYTHONPATH=src python benchmarks/run.py --smoke --ci
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable both as `python -m benchmarks.run` and `python benchmarks/run.py`
_REPO = os.path.join(os.path.dirname(__file__), "..")
for _p in (_REPO, os.path.join(_REPO, "src")):
    if os.path.abspath(_p) not in (os.path.abspath(q) for q in sys.path):
        sys.path.insert(0, _p)

#: every smoke suite the consolidated CI step runs: (name, module, out file)
SMOKE_SUITES = ("multijob", "dataplane", "fpe", "jct", "placement", "sim",
                "faults", "churn")


def run_smoke(out_dir: str, *, ci: bool = False) -> dict:
    """Run every bench suite's smoke config; write all BENCH_*.json plus
    the observability artifacts (trace.json / metrics.json / dashboard,
    DESIGN.md §11) for the CI artifact upload."""
    import importlib

    from repro.obs import report as obs_report
    from repro.obs import trace as obs_trace

    os.makedirs(out_dir, exist_ok=True)
    obs_trace.enable()
    tracer = obs_trace.get_tracer()
    results = {}
    for name in SMOKE_SUITES:
        mod = importlib.import_module(f"benchmarks.bench_{name}")
        t0 = time.perf_counter()
        with tracer.span(f"smoke:{name}", cat="bench"):
            rows = mod.smoke_rows()
        dt = time.perf_counter() - t0
        if not ci:
            mod.print_rows(rows)
        mod.write_out(rows, os.path.join(out_dir, f"BENCH_{name}.json"))
        print(f"smoke_{name},{dt*1e6:.0f},{len(rows)}rows")
        results[name] = rows
    paths = obs_report.write_obs_artifacts(
        out_dir, title="bench smoke observability")
    print("smoke_obs_artifacts,0," + ";".join(
        os.path.basename(p) for p in sorted(paths.values())))
    return results


def _timeit(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / reps
    return out, dt * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="consolidated CI smoke: every bench suite's smoke "
                         "config, all BENCH_*.json into --out-dir")
    ap.add_argument("--ci", action="store_true",
                    help="terse per-suite output (implies --smoke)")
    ap.add_argument("--out-dir",
                    default=os.path.join(os.path.dirname(__file__), "out"))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke or args.ci:
        run_smoke(args.out_dir, ci=args.ci)
        return

    from benchmarks import bench_collectives, paper_figs

    out_dir = args.out_dir
    os.makedirs(out_dir, exist_ok=True)
    results: dict = {}
    print("name,us_per_call,derived")

    # --- paper figures/tables ---------------------------------------------
    fig2a, us = _timeit(paper_figs.fig2a, reps=1)
    results["fig2a"] = fig2a
    cliff = next(r for r in fig2a if r["key_variety"] > r["capacity"] * 10)
    print(f"fig2a_reduction_cliff,{us:.0f},N=10C->R={cliff['simulated']}")

    fig2b, us = _timeit(paper_figs.fig2b, reps=1)
    results["fig2b"] = fig2b
    gain = fig2b[-1]["end_to_end_reduction"] - fig2b[0]["end_to_end_reduction"]
    print(f"fig2b_multihop_gain,{us:.0f},4hops-1hop={gain:.4f}")

    eq, us = _timeit(paper_figs.eq1_eq2, reps=1)
    results["eq1_eq2"] = eq
    print(f"eq1_fixed_format_waste,{us:.0f},{eq['eq1_fixed20_random_pairs']}x_vs_"
          f"{eq['switchagg_encoding_random_pairs']}x")
    print(f"eq2_header_overhead,{us:.0f},rmt={eq['eq2_rmt200B_overhead']}")

    fig9, us = _timeit(paper_figs.fig9, reps=1)
    results["fig9"] = fig9
    m_best = max(r["reduction"] for r in fig9 if r["mode"] == "M-multilevel"
                 and r["dist"] == "zipf")
    s_best = max(r["reduction"] for r in fig9 if r["mode"].startswith("S")
                 and r["dist"] == "uniform")
    print(f"fig9_multilevel_zipf_best,{us:.0f},R={m_best}")
    print(f"fig9_sram_uniform_best,{us:.0f},R={s_best}")

    t2, us = _timeit(paper_figs.table2, reps=1)
    results["table2"] = t2
    print(f"table2_evict_rate,{us:.0f},max={max(r['evict_rate'] for r in t2)}")

    results["table3"] = paper_figs.table3()
    print("table3_stage_delays,0,analytic")

    f10, us = _timeit(paper_figs.fig10_11, reps=1)
    results["fig10_11"] = f10
    print(f"fig10_jct_saved,{us:.0f},{f10[-1]['jct_saved']:.0%}@16GB")

    # --- TPU-domain collective accounting ---------------------------------
    tt, us = _timeit(bench_collectives.traffic_table, reps=1)
    results["collective_traffic"] = tt
    print(f"collective_dcn_cut,{us:.0f},dense_tree={tt[0]['dcn_cut_vs_flat']:.4f}")
    results["compression_payload"] = bench_collectives.compression_payload_table()

    # --- kernel micro-benchmarks (CPU walltime; TPU perf is §Roofline) ----
    import jax.numpy as jnp

    from repro.core import kvagg

    keys = jnp.asarray(np.random.default_rng(0).integers(0, 512, 4096),
                       jnp.int32)
    vals = jnp.ones((4096,), jnp.float32)

    def node():
        return kvagg.two_level_aggregate(keys, vals, capacity=128, ways=4
                                         ).n_out.block_until_ready()

    _, us = _timeit(node, reps=3)
    print(f"kvagg_node_4096pairs,{us:.0f},{4096 / us:.2f}pairs_per_us")

    from repro.kernels import ops

    def pallas_node():
        return ops.two_level_aggregate(keys, vals, capacity=128, ways=4,
                                       block_n=512, interpret=True
                                       ).n_out.block_until_ready()

    _, us = _timeit(pallas_node, reps=1)
    print(f"kvagg_pallas_interpret,{us:.0f},correctness_mode")

    # --- cascade dataplane: capacity x levels x op (DESIGN.md §6) ---------
    from benchmarks import bench_dataplane

    dp_rows = bench_dataplane.sweep(
        ops=("sum", "max", "count", "mean", "logsumexp"),
        capacities=(32, 128), levels=(1, 2), n=2048, variety=512,
        dist="zipf", backend="jnp", reps=1)
    results["dataplane"] = dp_rows
    bench_dataplane.write_out(
        dp_rows, os.path.join(out_dir, "BENCH_dataplane.json"))
    best = max(dp_rows, key=lambda r: r["end_to_end_reduction"])
    print(f"dataplane_best_reduction,{best['wall_us']:.0f},"
          f"{best['op']}xL{best['levels']}xC{best['capacity_per_node']}"
          f"=R{best['end_to_end_reduction']:.3f}")

    # --- FPE throughput: scan oracle vs batched fast path (DESIGN.md §8) --
    from benchmarks import bench_fpe

    fpe_rows = bench_fpe.sweep(
        ops=("sum", "mean"), lengths=(8192,), ways_list=(4,),
        backends=("jnp",), variety=1024, capacity=256, dist="zipf", reps=2)
    fpe_rows.append(bench_fpe.headline_row(reps=2, check=False))
    results["fpe"] = fpe_rows
    bench_fpe.write_out(fpe_rows, os.path.join(out_dir, "BENCH_fpe.json"))
    hl = fpe_rows[-1]
    print(f"fpe_fast_path,{hl['fast_us']:.0f},"
          f"{hl['speedup']}x_vs_scan@100k_zipf")

    # --- packet-level JCT: switchagg vs host-only (DESIGN.md §7) ----------
    from benchmarks import bench_jct

    jct_rows = bench_jct.sweep(
        fanouts=[(4, 2)], loss_rates=(0.0, 0.01), varieties=(512,),
        per_mapper=128, capacity=128, records_per_packet=32)
    results["jct"] = jct_rows
    bench_jct.write_out(jct_rows, os.path.join(out_dir, "BENCH_jct.json"))
    best_jct = max(jct_rows, key=lambda r: r["jct_saved"])
    print(f"jct_saved,{best_jct['wall_us']:.0f},"
          f"{best_jct['jct_saved']:.1%}@loss{best_jct['loss_rate']}")

    # --- multi-job congestion-aware controller (DESIGN.md §3) -------------
    from benchmarks import bench_multijob

    mj, us = _timeit(lambda: bench_multijob.run_once(
        4, budget_mb=128.0, partition="weighted", base_mb=256.0), reps=1)
    results["multijob_4"] = mj
    print(f"multijob_scarce_cut,{us:.0f},{mj['total_scarce_mb']:.1f}MiB_vs_"
          f"flat_{mj['flat_total_scarce_mb']:.1f}MiB")

    # --- roofline summary (from dry-run artifacts, if present) ------------
    try:
        from benchmarks import roofline

        rows = roofline.load(pod="1", mode="tree")
        if rows:
            worst = min(rows, key=lambda r: r["fraction"])
            print(f"roofline_cells_pod1,{0},{len(rows)}")
            print(f"roofline_worst_fraction,0,{worst['arch']}x{worst['shape']}"
                  f"={worst['fraction']:.4f}")
            results["roofline_pod1"] = rows
    except Exception as e:  # artifacts absent on a fresh checkout
        print(f"roofline_summary,0,skipped({e})")

    with open(os.path.join(out_dir, "results.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# full results -> {os.path.join(out_dir, 'results.json')}")


if __name__ == "__main__":
    main()
