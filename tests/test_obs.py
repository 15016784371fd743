"""Observability layer: tracing, metrics registry, dashboard (DESIGN.md §11).

Pins the three contracts the obs layer ships with:

* **Chrome-trace schema** — ``Tracer.to_chrome()`` is loadable trace-event
  JSON (Perfetto), with well-formed nesting per (pid, tid) lane and every
  simulated-time span inside ``[0, jct]``;
* **zero overhead when disabled** — a disabled tracer records nothing,
  hands out the no-op singleton, and allocates zero bytes inside
  ``repro.obs.trace`` (the throughput side of the same contract is
  floor-gated by ``bench_sim.py``'s ``obs_overhead`` cell);
* **telemetry parity** — the node and vectorized sim engines publish
  bit-identical metric series for the same job, loss included: the
  DESIGN.md §10 parity contract extended to telemetry.
"""

import dataclasses
import gc
import json
import pathlib
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from repro.core import dataplane, planner
from repro.core import reduction_model as rm
from repro.net import sim as netsim
from repro.net import simulate
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace


def _small_plan(caps=(32, 32), op="sum"):
    return dataplane.CascadePlan(op=op, levels=tuple(
        dataplane.LevelSpec(capacity=c) for c in caps))


def _run_small_job(tag="job"):
    keys = rm.zipf_keys(256, 64, skew=0.99, seed=0).astype(np.int32)
    vals = np.ones((256,), np.float32)
    return simulate(netsim.JobSpec(
        keys=keys, values=vals, fanins=(2, 2), plan=_small_plan(),
        cfg=netsim.NetConfig(records_per_packet=8, exact_stream=True),
        tag=tag))


def _run_lossy_fat_tree(engine):
    """A lossy fat-tree job — retransmit/gap/duplicate series non-zero."""
    ft = planner.FatTreeTopology(pods=4, tors_per_pod=2, hosts_per_tor=2,
                                 oversubscription=4.0, table_pairs=256)
    n = ft.n_hosts * 16
    keys = rm.zipf_keys(n, 64, skew=0.99, seed=1).astype(np.int32)
    vals = np.ones((n,), np.float32)
    placement = planner.place_aggregation_tree(
        ft, per_host_pairs=16, key_variety=64, policy="full")
    cfg = netsim.NetConfig(records_per_packet=4, exact_stream=True,
                           loss_rate=0.02, seed=3, window=4, engine=engine)
    return simulate(ft, keys, vals, placement=placement, cfg=cfg)


# -- trace export schema ----------------------------------------------------

def test_trace_chrome_export_schema():
    with obs_trace.scoped_tracer() as tr:
        with tr.span("outer", cat="wall", args={"k": 1}):
            with tr.span("inner", cat="wall"):
                pass
        pid = tr.new_track("sim test")
        tr.name_thread(pid, 0, "L0 transport")
        tr.add_span("transport", 0.0, 1.5e-3, cat="sim.transport", pid=pid)
        doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    json.loads(json.dumps(doc))  # round-trips as JSON
    # metadata names the wall-clock process and the sim track
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"name": "process_name", "ph": "M", "pid": obs_trace.WALL_PID,
            "tid": 0, "args": {"name": "wall-clock"}} in meta
    assert any(e["name"] == "process_name" and e["pid"] == pid
               for e in meta)
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "L0 transport" for e in meta)
    for e in evs:
        if e["ph"] == "M":  # metadata events carry no timestamp
            assert {"name", "pid", "tid", "args"} <= e.keys()
            continue
        assert {"name", "ph", "ts", "pid", "tid"} <= e.keys()
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
            assert "cat" in e
    # virtual-time spans are exported in microseconds
    tx = next(e for e in evs if e["name"] == "transport")
    assert tx["ts"] == 0.0 and tx["dur"] == pytest.approx(1.5e3)


def _assert_well_nested(events):
    """Per (pid, tid) lane, "X" spans either nest or are disjoint."""
    lanes = {}
    for e in events:
        if e["ph"] == "X":
            lanes.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    assert lanes
    eps = 1e-6
    for lane, spans in lanes.items():
        for i, (a0, a1) in enumerate(spans):
            for b0, b1 in spans[i + 1:]:
                disjoint = a1 <= b0 + eps or b1 <= a0 + eps
                nested = ((a0 >= b0 - eps and a1 <= b1 + eps)
                          or (b0 >= a0 - eps and b1 <= a1 + eps))
                assert disjoint or nested, (
                    f"partial overlap on lane {lane}: "
                    f"[{a0},{a1}] vs [{b0},{b1}]")


def test_sim_trace_spans_within_jct_and_well_nested():
    with obs_trace.scoped_tracer() as tr:
        res = _run_small_job()
        events = [e for e in tr.events]
    sim_events = [e for e in events if e["pid"] >= 1]
    assert sim_events, "sim run recorded no virtual-time spans"
    jct_us = res.jct_s * 1e6
    for e in sim_events:
        assert e["ts"] >= -1e-6
        assert e["ts"] + e.get("dur", 0.0) <= jct_us * (1 + 1e-9) + 1e-6
    _assert_well_nested(events)


def test_each_sim_run_gets_its_own_track():
    with obs_trace.scoped_tracer() as tr:
        _run_small_job(tag="a")
        _run_small_job(tag="b")
        pids = {e["pid"] for e in tr.events if e["pid"] >= 1}
        names = [m["args"]["name"] for m in tr._meta
                 if m["name"] == "process_name"]
    assert len(pids) == 2
    assert any("a" in n for n in names) and any("b" in n for n in names)


# -- disabled tracer: the zero-overhead contract ----------------------------

def test_disabled_tracer_records_nothing_and_reuses_singleton():
    tr = obs_trace.Tracer()  # disabled by default
    s1 = tr.span("x", cat="y", args={"big": list(range(10))})
    s2 = tr.span("z")
    assert s1 is s2 is obs_trace._NULL_SPAN
    with s1:
        pass
    tr.add_span("a", 0.0, 1.0)
    tr.add_wall_span("b", 0.0, 1.0)
    tr.name_thread(1, 0, "lane")
    assert tr.events == []
    assert tr._meta == []
    assert tr.to_chrome()["traceEvents"][1:] == []  # wall meta only


def test_disabled_tracer_allocates_zero_bytes():
    tr = obs_trace.Tracer()
    for _ in range(5):  # warm caches (method wrappers, etc.)
        with tr.span("warm"):
            pass
        tr.add_span("warm", 0.0, 1.0)
    filt = [tracemalloc.Filter(True, obs_trace.__file__)]
    tracemalloc.start()
    try:
        snap0 = tracemalloc.take_snapshot().filter_traces(filt)
        for _ in range(200):
            with tr.span("x", cat="y"):
                pass
            tr.add_span("x", 0.0, 1.0)
            tr.add_wall_span("x", 0.0, 1.0)
        snap1 = tracemalloc.take_snapshot().filter_traces(filt)
    finally:
        tracemalloc.stop()
    diff = snap1.compare_to(snap0, "lineno")
    leaked = sum(s.size_diff for s in diff)
    assert leaked <= 0, f"disabled tracer allocated {leaked}B: {diff[:5]}"


# -- metrics registry -------------------------------------------------------

def test_registry_label_identity_and_kind_conflict():
    with obs_metrics.scoped() as reg:
        reg.counter("t.x_total", b="2", a="1").inc(3)
        reg.counter("t.x_total", a="1", b="2").inc(4)  # same series
        assert reg.value("t.x_total", a="1", b="2") == 7.0
        reg.gauge("t.g_s", job="j").set(1.5)
        assert reg.value("t.g_s", job="j") == 1.5
        h = reg.histogram("t.h")
        h.observe(1.0)
        h.observe(3.0)
        snap = reg.value("t.h")
        assert snap == {"count": 2, "sum": 4.0, "min": 1.0, "max": 3.0,
                        "mean": 2.0}
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("t.x_total")


def test_collect_is_deterministic_across_publish_order():
    with obs_metrics.scoped() as a:
        a.counter("m.n_total", x="1").inc(1)
        a.gauge("m.g", y="2").set(5)
    with obs_metrics.scoped() as b:
        b.gauge("m.g", y="2").set(5)
        b.counter("m.n_total", x="1").inc(1)
    assert a.collect() == b.collect()
    assert a.collect()  # non-empty


# -- engine telemetry parity ------------------------------------------------

def _normalized_series(reg):
    # the compile counters depend on what the process compiled before the
    # job, not on the job: they are no part of the parity contract
    series = [s for s in reg.collect() if not s["name"].startswith("jax.")]
    for s in series:
        s["labels"].pop("engine", None)
    return series


def test_sim_engines_publish_identical_metric_series():
    """Node and vectorized runs of the same lossy fat-tree job emit the
    SAME metric series (names, labels, values) — telemetry parity."""
    with obs_metrics.scoped() as reg_n:
        _run_lossy_fat_tree("node")
    with obs_metrics.scoped() as reg_v:
        _run_lossy_fat_tree("vectorized")
    sn, sv = _normalized_series(reg_n), _normalized_series(reg_v)
    assert sn, "sim run published no metrics"
    assert sn == sv
    # loss actually exercised the transport series
    retx = [s for s in sn if s["name"] == "transport.retransmissions_total"]
    assert retx and sum(s["value"] for s in retx) > 0


def test_sim_publishes_expected_series_names():
    with obs_metrics.scoped() as reg:
        res = _run_small_job(tag="t0")
    names = {s["name"] for s in reg.collect()}
    for want in ("sim.job.jct_s", "sim.job.delivered_records_total",
                 "sim.level.records_in_total", "sim.level.evictions_total",
                 "sim.link.wire_bytes_total", "transport.timeouts_total"):
        assert want in names, f"missing series {want}"
    assert reg.value("sim.job.jct_s", job="t0", engine="node", agg="1",
                     op="sum") == res.jct_s


# -- publishers in the other layers -----------------------------------------

def test_dataplane_and_planner_publish():
    with obs_metrics.scoped() as reg:
        dataplane.simulate_plan(_small_plan(), data_amount=512,
                                key_variety=64, dist="zipf")
        ft = planner.FatTreeTopology(pods=4, tors_per_pod=2,
                                     hosts_per_tor=2, oversubscription=4.0,
                                     table_pairs=256)
        planner.place_aggregation_tree(ft, per_host_pairs=16,
                                       key_variety=64, policy="auto")
        names = {s["name"] for s in reg.collect()}
    for want in ("dataplane.level.records_in_total",
                 "dataplane.level.reduction",
                 "dataplane.level.predicted_reduction",
                 "planner.placement.candidates_scored_total",
                 "planner.placement.scarce_uplink_bytes"):
        assert want in names, f"missing series {want}"


def test_instrumented_step_counts_calls_and_forwards_attrs():
    def step(x):
        return x + 1

    step.custom_marker = "here"
    with obs_metrics.scoped() as reg:
        wrapped = obs_metrics.instrument_step(step, name="train.step",
                                              labels={"mode": "t"})
        assert wrapped(1) == 2
        assert wrapped(2) == 3
        assert wrapped.custom_marker == "here"
        assert reg.value("train.step.calls_total", mode="t") == 2.0
        assert reg.value("train.step.wall_s", mode="t")["count"] == 2


# -- dashboard artifacts ----------------------------------------------------

def test_write_obs_artifacts_end_to_end(tmp_path):
    with obs_metrics.scoped() as reg, obs_trace.scoped_tracer() as tr:
        _run_small_job(tag="dash")
        dataplane.simulate_plan(_small_plan(), data_amount=512,
                                key_variety=64, dist="zipf")
        paths = obs_report.write_obs_artifacts(
            tmp_path, registry=reg, tracer=tr, title="test dashboard")
    assert set(paths) == {"metrics", "trace", "dashboard_md",
                          "dashboard_html"}
    with open(paths["trace"]) as f:
        trace = json.load(f)
    assert trace["traceEvents"]
    with open(paths["metrics"]) as f:
        metrics = json.load(f)
    assert metrics["metrics"]
    html = open(paths["dashboard_html"]).read()
    md = open(paths["dashboard_md"]).read()
    for doc in (html, md):
        assert "JCT" in doc
        assert "reduction" in doc.lower()
    assert "test dashboard" in html
    # the Eq.3 join made it in: predicted vs simulated per level
    assert "predicted" in md.lower()


def test_dashboard_renders_without_trace(tmp_path):
    with obs_metrics.scoped() as reg:
        _run_small_job(tag="mtr")
        md = obs_report.dashboard_markdown(reg.collect(), None)
    assert "mtr" in md


# -- the profiler sink: spans on the device trace's clock -------------------

def _captured_host_events(fn, tmp_path):
    """Run ``fn`` inside a JAX profiler capture; the host events recorded,
    as (name, stats) pairs."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_disabled_span_annotates_a_running_capture(tmp_path):
    tr = obs_trace.Tracer()  # disabled: the capture alone turns it on

    def spans():
        assert obs_trace.capturing()
        with tr.span("t.outer", args={"level": 2, "what": "x"}):
            with tr.span("t.inner"):
                pass

    events = _captured_host_events(spans, tmp_path)
    got = {n: s for n, s in events if n.startswith("t.")}
    assert got == {"t.outer": {"level": 2, "what": "x"}, "t.inner": {}}
    assert tr.events == []  # the Chrome sink stays off
    assert not obs_trace.capturing()
    assert tr.span("t.after") is obs_trace._NULL_SPAN


def test_enabled_span_feeds_both_sinks(tmp_path):
    with obs_trace.scoped_tracer() as tr:
        def spans():
            with tr.span("t.both", cat="c", args={"slots": 8}):
                pass

        events = _captured_host_events(spans, tmp_path)
        chrome = [e for e in tr.events if e["name"] == "t.both"]
    assert ("t.both", {"slots": 8}) in events
    assert len(chrome) == 1 and chrome[0]["args"] == {"slots": 8}


def test_gc_collections_are_spans_only_while_a_sink_is_on():
    with obs_trace.scoped_tracer() as tr:
        tr.disable()
        gc.collect(0)
        assert tr.events == []
        tr.enable()
        gc.collect(1)
        gcs = [e for e in tr.events if e["name"] == "python.gc"]
    assert gcs and gcs[-1]["args"] == {"generation": 1}
    assert gcs[-1]["cat"] == "gc" and gcs[-1]["dur"] >= 0.0


def test_obs_never_imports_jax():
    code = ("import sys, gc, repro.obs as o\n"
            "t = o.get_tracer()\n"
            "assert t.span('x') is o.trace._NULL_SPAN\n"
            "assert not o.trace.capturing()\n"
            "gc.collect()\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = {"PYTHONPATH": str(pathlib.Path(obs_trace.__file__).parents[2])}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
