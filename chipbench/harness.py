"""The benchmark's run of one cell, driven by ``BENCHMARK.json``.

Nothing here names a configuration, traffic mix or metric.  A cell's
names lead to files of their own under this directory:

* ``BENCHMARK.json`` ``configs[].file`` — the configuration (sizes, the
  placement request, and the names of its ``generator`` and
  ``reference``);
* ``data/<generator>.py`` — ``generate(config, seed, prng_key)`` gives the
  job's stream (keys, values);
* ``references/<reference>.py`` — ``totals`` (the plain reference),
  ``control`` (the same in a lower precision) and ``LANES``;
* ``traffic/<traffic>.json`` — the traffic mix: which ``driver`` carries
  the jobs and that driver's parameters;
* ``drivers/<driver>.py`` — ``prepare(cell, keys, values)`` gives the job
  callable, and ``ENTRY`` names the ``dataplane`` function it drives;
* ``metrics/<metric>.py`` — ``read(window)`` gives the metric's value, or
  None where the run has nothing for it to read.

A run: make the stream from the seed, build the job, run one whole job
as warm-up (set-up ends there), then start jobs back to back until the
window's seconds have passed; the job running then completes and counts.
After the window: read the device's memory peak, free the program's
state, and compare every job of the window with the reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import compare as cmp
from chipbench import tracereduce
from chipbench.jobs import JobResult, span

HERE = pathlib.Path(__file__).resolve().parent
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    name: str
    chips: int
    config: dict
    traffic: dict
    bench: dict
    base: pathlib.Path  # the directory the named files are found under
    plan: object = None

    def module(self, kind: str, name: str):
        return load_module(self.base / kind / f"{name}.py")

    def metrics(self, section: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold '-')."""
    key = "chipbench._by_path." + str(path).replace("/", "_").replace(
        ".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def load_cell(root: pathlib.Path, workload: str,
              base: pathlib.Path | None = None) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``; its files are
    found under ``base`` (default: ``root/chipbench``)."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    base = pathlib.Path(base) if base else root / "chipbench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (base / "traffic" / f"{w['traffic']}.json").read_text()),
        bench=bench, base=base)


def build_plan(config: dict):
    """``plan()`` on the configuration's fat tree, then the cascade of the
    placement: the normal front door of a job."""
    from repro.core import dataplane, plan, planner

    ft = planner.FatTreeTopology(**config["fat_tree"])
    req = planner.LaunchRequest(
        job_id=1, n_workers=ft.n_hosts,
        expected_pairs=max(1, config["expected_records"] // ft.n_hosts),
        key_variety=config["key_variety"], op=config["op"])
    job = plan(req, ft, policy=config["policy"])
    return dataplane.plan_from_placement(job.configure, op=config["op"],
                                         ways=config["ways"])


def prng_key(seed: int):
    """A JAX key from any non-negative seed, wider than 32 bits too."""
    import jax

    key = jax.random.key(0)
    for word in np.random.SeedSequence(seed).generate_state(2, np.uint32):
        key = jax.random.fold_in(key, word)
    return key


def check_devices(chips: int):
    """The devices of the run; raises :class:`NoChip` unless JAX finds at
    least ``chips`` TPUs."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def peaks_for(kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class Window:
    """What a metric reader reads: the window's jobs and clocks, and in a
    traced run the reduced trace and the device's peaks."""

    cell: Cell
    jobs: list[JobResult]
    elapsed_s: float  # window start to the end of its last job
    setup_s: float
    lanes: int
    trace: tracereduce.Reduced | None = None
    peaks: dict | None = None


class CompileCounter:
    """Counts the programs JAX builds (compiled or loaded from the
    persistent cache) while it is on."""

    def __init__(self):
        self.on = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def read_metrics(cell: Cell, window: Window, section: str) -> dict:
    out = {}
    for m in cell.metrics(section):
        value = cell.module("metrics", m["name"]).read(window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             interpret: bool = False, keep_trace=None) -> dict:
    """One run of a cell; returns the result line's object.

    ``t_start`` is the process's start on the ``time.perf_counter`` clock.
    ``require_tpu=False`` and ``interpret=True`` are for the CPU tests;
    ``keep_trace`` is a file the traced run's ``.xplane.pb`` is copied to.
    """
    import jax

    workload = cell.name
    devs = check_devices(cell.chips) if require_tpu else jax.devices()
    cell.plan = build_plan(cell.config)
    log(f"[setup] {workload}: {cell.plan.describe()}, "
        f"{cell.config['ways']} ways, seed {seed}")
    reference = cell.module("references", cell.config["reference"])
    keys, values = cell.module("data", cell.config["generator"]).generate(
        cell.config, seed, prng_key(seed))
    job = cell.module("drivers", cell.traffic["driver"]).prepare(
        cell, keys, values, interpret=interpret)
    with span("warmup"):
        warm = job()
    log(f"[setup] warm-up job: {warm.records_sent} records, per level in "
        f"{list(warm.level_in)} out {list(warm.level_out)}")
    del warm

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    jobs: list[JobResult] = []
    with CompileCounter() as compiles:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles.on = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        ends = []
        while True:
            with span("job"):
                jobs.append(job())
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
        elapsed = ends[-1]
        compiles.on = False
        if trace:
            jax.profiler.stop_trace()
    log(f"[window] {len(jobs)} jobs in {elapsed:.3f} s; programs compiled "
        f"or loaded inside the window: {compiles.count}")
    log(f"[window] job seconds {np.diff([0.0] + ends).round(4).tolist()}")
    ingests = np.array([t for j in jobs for t in j.ingest_s])
    if ingests.size:
        med = float(np.median(ingests))
        log(f"[window] {ingests.size} ingests: median {med * 1e3:.3f} ms, "
            f"longest {ingests.max() * 1e3:.3f} ms, "
            f"{int((ingests > 2 * med).sum())} over twice the median")

    stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    keys, values = np.asarray(keys), np.asarray(values)
    del job
    ref_ids, ref_tot = reference.totals(keys, values)
    numbers = [cmp.compare_job(j, ref_ids, ref_tot) for j in jobs]
    failed = sum(not cmp.passes(n) for n in numbers)
    checks = cmp.worst(numbers)

    window = Window(cell, jobs, elapsed, setup_s, reference.LANES)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": failed == 0 and bool(jobs),
              "attempted": len(jobs), "failed": failed}
    if trace:
        window.peaks = peaks_for(devs[0].device_kind) if require_tpu else None
        xplane = tracereduce.find_xplane(trace_dir)
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        reduced = tracereduce.reduce(tracereduce.load(xplane))
        shutil.rmtree(trace_dir, ignore_errors=True)
        window.trace = reduced
        result["metrics"] = read_metrics(cell, window, "per_layer")
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result["breakdown"] = {
                "device_ops": [list(x) for x in reduced.top_ops(10)],
                "idle_gaps": [list(x) for x in reduced.idle_gaps()[:10]]}
            for name, sec in reduced.idle_by_span()[:10]:
                log(f"[trace] idle {sec:.6f} s while {name}")
    else:
        result["metrics"] = read_metrics(cell, window, "end_to_end")
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": cmp.LIMITS[k]}
                        for k, v in checks.items()}
    return result
