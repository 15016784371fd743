"""The stream loop's spans and counters (DESIGN.md §11): what a JAX
profiler capture of ``run_cascade_stream`` holds, and the registry's
slot and compile counters."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import dataplane
from repro.core.dataplane import CascadePlan, LevelSpec
from repro.obs import metrics as obs_metrics

CAPACITY, PAD, N = 64, 256, 2000
PLAN = CascadePlan(op="sum", levels=tuple(
    LevelSpec(capacity=CAPACITY) for _ in range(3)))


def _batches():
    keys = np.random.default_rng(0).integers(0, 500, N).astype(np.int32)
    vals = np.ones((N,), np.float32)
    return [(keys[i:i + PAD], vals[i:i + PAD]) for i in range(0, N, PAD)]


def _job():
    return dataplane.run_cascade_stream(iter(_batches()), PLAN,
                                        batch_pad=PAD, exact_stream=False)


def _program_events(path):
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("dataplane.")]


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One job before, one inside and one after a profiler capture."""
    tmp = tmp_path_factory.mktemp("capture")
    _job()
    with obs_metrics.scoped() as reg:
        jax.profiler.start_trace(str(tmp))
        try:
            res = _job()
        finally:
            jax.profiler.stop_trace()
    _job()
    (path,) = tmp.rglob("*.xplane.pb")
    return _program_events(path), reg, res


def test_stream_spans_reach_a_capture_with_their_args(captured):
    events, _, res = captured
    n_batches = len(_batches())
    count = collections.Counter(n for n, _ in events)
    # one job's spans: none from the jobs outside the capture
    assert count["dataplane.run_cascade_stream"] == 1
    assert count["dataplane.ingest"] == n_batches
    assert count["dataplane.flush"] == count["dataplane.root_combine"] \
        + 2 == 3
    ingests = [s for n, s in events if n == "dataplane.ingest"]
    assert [s["batch"] for s in ingests] == list(range(n_batches))
    assert sum(s["records"] for s in ingests) == N
    fpe = [s for n, s in events if n == "dataplane.fpe"]
    bpe = [s for n, s in events if n == "dataplane.bpe"]
    assert fpe and len(fpe) == len(bpe) == count["dataplane.level"]
    assert {s["level"] for s in fpe} == {0, 1, 2}
    assert all(s["slots"] == PAD and 0 < s["real"] <= PAD for s in fpe)
    assert all(s["slots"] == PAD + CAPACITY and 0 <= s["real"] <= s["slots"]
               for s in bpe)
    # real comes from the loop's own counts
    level_in = [sum(s["real"] for s in fpe if s["level"] == i)
                for i in range(3)]
    assert level_in == np.asarray(res.level_in).tolist()
    evicted = [sum(s["real"] for s in bpe if s["level"] == i)
               for i in range(3)]
    assert evicted == np.asarray(res.level_evict).tolist()
    fetch = collections.Counter(s["what"] for n, s in events
                                if n == "dataplane.fetch")
    assert fetch == {"prepare": n_batches, "evict_count": len(fpe),
                     "evictions": len(bpe), "table": 3}


def test_slots_counter_sums_the_padded_dispatches(captured):
    _, reg, res = captured
    fed = [len(_batches())]  # ingests per level: its batches, then the
    for _ in range(2):       # level below's evictions and its flush
        fed.append(fed[-1] + 1)
    for i, n in enumerate(fed):
        want = n * (PAD + PAD + CAPACITY)
        assert reg.value("dataplane.level.slots_total", op="sum",
                         source="stream", level=i) == want
    assert [reg.value("dataplane.level.records_in_total", op="sum",
                      source="stream", level=i)
            for i in range(3)] == np.asarray(res.level_in).tolist()


def test_compiles_are_counted_by_function():
    x = jnp.ones((7, 3, 5))  # a shape no other test compiles
    with obs_metrics.scoped() as reg:
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        hist = reg.find("jax.compile_s")
        count = reg.find("jax.compiles_total")
    assert [lbl for lbl, _ in count] == [{"fun": "jit(<lambda>)"}]
    assert count[0][1] == 1.0
    assert hist[0][1]["count"] == 1 and hist[0][1]["sum"] > 0
