"""The trace reduction: interval arithmetic on hand-made intervals, and the
whole reduction and the per-layer readers on small traces recorded on a
TPU v5e (``record_trace.py``)."""

import functools
import gzip
import pathlib
import shutil
import tempfile

import pytest

from chipbench import harness, tracereduce
from chipbench.jobs import JobResult
from chipbench.tracereduce import Labeller, Span, covered, gaps, merge

DATA = pathlib.Path(__file__).resolve().parent / "data"
BASE = DATA.parents[1]


def test_merge_covered_gaps():
    m = merge([(5, 8), (0, 2), (1, 3), (8, 9), (12, 20)])
    assert m == [(0, 3), (5, 9), (12, 20)]
    assert covered(m, 0, 20) == 3 + 4 + 8
    assert covered(m, 2, 13) == 1 + 4 + 1
    assert covered(m, 21, 30) == 0
    assert gaps(m, 0, 20) == [(3, 5), (9, 12)]
    assert gaps(m, -4, 25) == [(-4, 0), (3, 5), (9, 12), (20, 25)]
    lo, hi = 1, 18
    assert covered(m, lo, hi) + sum(e - s for s, e in gaps(m, lo, hi)) \
        == hi - lo


def test_labeller_takes_the_innermost_span():
    spans = [Span("job", 0, 100), Span("ingest", 10, 20),
             Span("ingest", 30, 40), Span("flush", 80, 95),
             Span("job", 100, 150), Span("result", 140, 150)]
    at = Labeller(spans)
    assert [at(t) for t in (5, 10, 19, 20, 35, 90, 99, 120, 145)] == [
        "job", "ingest", "ingest", "job", "ingest", "flush", "job", "job",
        "result"]
    assert at(-1) == at(150) == Labeller.OUTSIDE


@functools.cache
def test_short_names_and_self_times():
    text = ("%fusion.66 = s32[4390912]{0:T(1024)S(1)} fusion(s32[4390912]"
            "{0:T(1024)S(1)} %get-tuple-element.368), kind=kCustom")
    assert tracereduce.short_name(text) == "%fusion.66 fusion s32[4390912]"
    loop = tracereduce.Op("while", "", 0, 100)
    body = [tracereduce.Op("a", "", 10, 40), tracereduce.Op("b", "", 50, 60)]
    after = tracereduce.Op("c", "", 100, 130)
    ops = [loop, *body, after]
    tracereduce._self_times(ops)
    assert [o.self_ns for o in ops] == [60, 30, 10, 30]


def _reduced(cell):
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / "trace.xplane.pb"
        with gzip.open(DATA / f"{cell}.xplane.pb.gz") as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return tracereduce.reduce(tracereduce.load(path))


CELLS = ["hibench-wordcount.batch", "tpch-q18.stream"]


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_trace_reduces(cell):
    r = _reduced(cell)
    assert r is not None and len(r.busy) == 1  # one chip
    assert 0 < r.busy_s < r.window_s
    idle = sum(sec for _, sec in r.idle_gaps())
    assert idle + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    names = {n for n, _ in r.idle_by_span()}
    assert names <= {"job", "cascade", "result", "ingest", "flush",
                     Labeller.OUTSIDE}
    top = r.top_ops(10)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    # self times tile the union of the ops: nested ops count once
    union = sum(e - s for s, e in merge((o.start, o.end) for o in r.ops))
    assert sum(o.self_ns for o in r.ops) == pytest.approx(union, rel=1e-6)
    assert all(n.startswith("jit_") for n, _ in top)


def _window(cell, reduced, jobs):
    c = harness.load_cell(BASE.parent, cell)
    return harness.Window(c, jobs, reduced.window_s, 1.0, 1, reduced,
                          harness.peaks_for("TPU v5 lite"))


def _jobs(reduced, level_in, level_out):
    n = sum(1 for s in reduced.spans if s.name == "job")
    return [JobResult(None, None, level_in[0], level_out[-1], level_in,
                      level_out, level_in[0])] * n


@pytest.mark.parametrize("cell", CELLS)
def test_readers_on_recorded_trace(cell):
    r = _reduced(cell)
    w = _window(cell, r, _jobs(r, (16384, 4000, 3000), (4000, 3000, 3000)))
    c = w.cell
    read = {m["name"]: c.module("metrics", m["name"]).read(w)
            for m in c.metrics("per_layer")}
    assert set(read) == {m["name"] for m in c.metrics("per_layer")}
    for name, v in read.items():
        assert v is not None, name
        if name.endswith("_pct") or name.endswith("roofline"):
            assert 0 < v <= 100, (name, v)
    if "batch_ingest_host_ms" in read:
        spans = [s for s in r.spans if s.name == "ingest"]
        longest = max(s.end - s.start for s in spans) / 1e6
        assert 0 < read["batch_ingest_host_ms"] <= longest


def test_fpe_kernel_is_found_by_name():
    r = _reduced("hibench-wordcount.batch")
    reader = harness.load_module(BASE / "metrics" / "fpe_kernel_roofline.py")
    hits = reader.kernel_ops(r.ops)
    assert len({o.name for o in hits}) == 3  # one kernel per placed level
    # the stream path bypasses the kernel: nothing there to read
    assert not reader.kernel_ops(_reduced("tpch-q18.stream").ops)
