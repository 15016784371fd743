"""The readers of the program's own spans (``attribute.py``) and of its
compile histogram (``metrics/compile_s.py``), and every per-layer reader's
value on the recorded traces of the first benchmark, pinned."""

import gzip
import pathlib
import shutil
import tempfile
import time

import numpy as np
import pytest

from chipbench import attribute, harness, tracereduce
from chipbench.tests.test_chipbench_cells import SEED, tiny_cell
from chipbench.tests.test_chipbench_trace import (CELLS, _jobs, _reduced,
                                                  _window)
from repro.obs import metrics as obs_metrics

DATA = pathlib.Path(__file__).resolve().parent / "data"
BASE = DATA.parents[1]

#: what every reader gave on the recorded traces when the program recorded
#: no spans of its own; adding the program's spans must change none
PINNED = {
    "hibench-wordcount.batch": {
        "device_idle_pct": 11.362894052861439,
        "node_roofline": 0.0014596944896522357,
        "fpe_kernel_roofline": 0.0024955048161630425},
    "tpch-q18.stream": {
        "device_idle_pct": 91.28443029193662,
        "node_roofline": 0.005118923514305145,
        "batch_ingest_host_ms": 9.285999},
}


@pytest.mark.parametrize("cell", CELLS)
def test_trace_readers_keep_their_values(cell):
    r = _reduced(cell)
    w = _window(cell, r, _jobs(r, (16384, 4000, 3000), (4000, 3000, 3000)))
    read = {name: w.cell.module("metrics", name).read(w)
            for name in PINNED[cell]}
    assert read == PINNED[cell]
    assert r.window_s == {"hibench-wordcount.batch": 0.050407704,
                          "tpch-q18.stream": 0.073092032}[cell]


def test_compile_s_sums_the_programs_histogram():
    cell = harness.load_cell(BASE.parent, CELLS[1])
    read = cell.module("metrics", "compile_s").read
    w = harness.Window(cell, [], 1.0, 1.0, 1)
    with obs_metrics.scoped() as reg:
        assert read(w) == 0.0  # nothing built yet
        obs_metrics.record_compile(obs_metrics.JAX_COMPILE_EVENT, 1.25,
                                   fun_name="jit(a)")
        obs_metrics.record_compile(obs_metrics.JAX_COMPILE_EVENT, 0.5)
        obs_metrics.record_compile("/jax/other", 9.0, fun_name="jit(a)")
        assert read(w) == 1.75
        assert reg.value("jax.compiles_total", fun="jit(a)") == 1.0


@pytest.fixture(scope="module")
def tiny_stream_run():
    """A tiny traced run of the stream cell on the CPU, its trace kept."""
    with tempfile.TemporaryDirectory() as tmp, obs_metrics.scoped() as reg:
        path = pathlib.Path(tmp) / "trace.xplane.pb"
        cell = tiny_cell(CELLS[1])
        result = harness.run_cell(cell, SEED, 0.3, True,
                                  t_start=time.perf_counter(),
                                  require_tpu=False, interpret=True,
                                  keep_trace=path)
        r = tracereduce.reduce(tracereduce.load(path))
        yield cell, result, r, attribute.load_program_spans(path), reg


def test_empty_slot_pct_equals_the_shapes_and_counters(tiny_stream_run):
    cell, result, r, spans, reg = tiny_stream_run
    assert result["correct"] and "compile_s" in result["metrics"]
    jobs = result["attempted"] + 1  # the window's and the warm-up job
    pad = cell.traffic["batch_records"]
    caps = [s.capacity for s in cell.plan.levels]
    assert all(s.enabled and s.bpe and s.capacity for s in cell.plan.levels)
    # per job, each level's FPE takes ``pad`` slots per ingest and its BPE
    # ``pad + capacity``; the leaf ingests every host batch
    fpe = [s for s in spans if s.name == "dataplane.fpe"]
    per_level = [sum(1 for s in fpe if s.args["level"] == i)
                 // result["attempted"] for i in range(len(caps))]
    assert per_level[0] == sum(1 for s in spans
                               if s.name == "dataplane.ingest") \
        // result["attempted"]
    slots = sum(n * (pad + pad + c) for n, c in zip(per_level, caps))
    assert sum(v for _, v in reg.find("dataplane.level.slots_total")) \
        == jobs * slots
    real = sum(v for _, v in reg.find("dataplane.level.records_in_total")) \
        + sum(v for _, v in reg.find("dataplane.level.evictions_total"))
    want = 100.0 * (1.0 - real / (jobs * slots))
    assert attribute.empty_slot_pct(spans) == pytest.approx(want, rel=1e-12)
    assert attribute.readings(r, spans)["empty_slot_pct"] \
        == pytest.approx(want, rel=1e-12)


def test_ingest_idle_splits_into_fetch_and_loop(tiny_stream_run):
    # no device plane on the CPU: every host nanosecond reads as idle
    _, _, r, spans, _ = tiny_stream_run
    spans = attribute.in_window(spans, r)
    per = attribute.ingest_idle(r, spans)
    ingests = [s for s in spans if s.name == "dataplane.ingest"]
    assert len(per) == len(ingests) > 0
    for (fetch, loop), ing in zip(per, ingests):
        fetched = sum(f.end - f.start for f in spans
                      if f.name == "dataplane.fetch"
                      and ing.start <= f.start < ing.end)
        assert fetch == pytest.approx(fetched / 1e6, rel=1e-9)
        assert fetch + loop == pytest.approx((ing.end - ing.start) / 1e6,
                                             rel=1e-9)
        assert 0 < fetch < fetch + loop
    got = attribute.readings(r, spans)
    assert got["ingests"] == len(per)
    assert got["ingest_fetch_idle_ms"] == pytest.approx(
        float(np.median([f for f, _ in per])), rel=1e-12)


@pytest.fixture(scope="module")
def recorded_spans():
    """The stream cell's small trace recorded on a TPU v5e with the
    program's spans (``record_trace.py``)."""
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / "trace.xplane.pb"
        with gzip.open(DATA / "tpch-q18.stream.spans.xplane.pb.gz") as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        yield (tracereduce.reduce(tracereduce.load(path)),
               attribute.load_program_spans(path))


def test_recorded_program_spans_carry_their_args(recorded_spans):
    r, spans = recorded_spans
    spans = attribute.in_window(spans, r)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert {n: len(v) for n, v in by.items()} == {
        "dataplane.run_cascade_stream": 1, "dataplane.ingest": 5,
        "dataplane.level": 14, "dataplane.fpe": 14, "dataplane.bpe": 14,
        "dataplane.fetch": 36, "dataplane.flush": 3,
        "dataplane.root_combine": 1}
    assert [s.args["batch"] for s in by["dataplane.ingest"]] == list(range(5))
    assert all(s.args["slots"] == 1024 for s in by["dataplane.fpe"])
    assert all(s.args["slots"] == 2048 for s in by["dataplane.bpe"])
    assert {s.args["what"] for s in by["dataplane.fetch"]} == {
        "prepare", "evict_count", "evictions", "table"}
    # the benchmark's ingest spans hold the program's
    outer = [s for s in r.spans if s.name == "ingest"]
    for ing in by["dataplane.ingest"]:
        assert any(o.start <= ing.start and ing.end <= o.end for o in outer)


def test_recorded_readings(recorded_spans):
    r, spans = recorded_spans
    got = attribute.readings(r, spans)
    assert got["ingests"] == 5
    assert got["ingest_fetch_idle_ms"] == pytest.approx(5.992269, rel=1e-9)
    assert got["ingest_loop_idle_ms"] == pytest.approx(2.812068, rel=1e-9)
    assert got["empty_slot_pct"] == pytest.approx(84.43545386904762,
                                                  rel=1e-12)
    # every idle nanosecond of the window is put down to one label, and
    # the benchmark's bare ingest holds what the program's spans do not
    by_span = dict(attribute.idle_by_span(r, spans))
    assert sum(by_span.values()) == pytest.approx(r.window_s - r.busy_s,
                                                  rel=1e-9)
    bare = by_span["ingest"]
    assert got["ingest_idle_in_program_pct"] == pytest.approx(
        100.0 * (1.0 - bare / got["ingest_idle_s"]), rel=1e-6)
    assert got["ingest_idle_in_program_pct"] > 99.0
    assert max(by_span, key=by_span.get) == \
        "dataplane.fetch(evictions, level 0)"
    top = attribute.idle_gaps(r, spans)[0]
    assert top[0] == "dataplane.fetch(prepare)"
