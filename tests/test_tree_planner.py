"""Aggregation tree + controller/planner (the paper's control plane)."""

import pytest

from repro.core import planner, tree as tree_lib
from repro.core.collectives import GradAggMode
from repro.launch.mesh import make_mesh
from repro.runtime.fault_tolerance import StragglerMonitor


# ---------------------------------------------------------------------------
# Tree construction.
# ---------------------------------------------------------------------------


def test_from_mesh_single_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    t = tree_lib.from_mesh(mesh)
    assert t.fanin == 1  # degenerate but total


def test_worker_tree_levels():
    t = tree_lib.worker_tree(7, fanin=4)
    # 7 workers, radix 4 -> 4 then 2 (paper Fig. 1: 7 mappers, 2 levels)
    assert [l.fanin for l in t.levels] == [4, 2]
    assert t.fanin == 8  # >= n_workers
    t1 = tree_lib.worker_tree(1, fanin=4)
    assert t1.fanin == 1
    with pytest.raises(ValueError):
        tree_lib.worker_tree(0, 4)


def test_worker_tree_describe():
    t = tree_lib.worker_tree(16, fanin=4)
    assert "lvl0(x4" in t.describe() and "root" in t.describe()


def test_traffic_model_from_tree():
    t = tree_lib.worker_tree(32, fanin=8)
    m = t.traffic_model(1 << 20)
    assert m.tree_reduction_at_root() > 0.8


# ---------------------------------------------------------------------------
# Controller: memory partitioning among trees (paper §4.2.2).
# ---------------------------------------------------------------------------


def test_controller_divides_memory_evenly():
    ctl = planner.Controller(combiner_budget_pairs=1024)
    t = tree_lib.worker_tree(8, 4)
    m1 = ctl.configure(planner.LaunchRequest(1, 8, 10000, 100), t)
    assert m1.fpe_capacity == 1024
    m2 = ctl.configure(planner.LaunchRequest(2, 8, 10000, 100), t)
    assert m2.fpe_capacity == 512
    assert ctl.active[1].fpe_capacity == 512  # re-partitioned
    ctl.release(1)
    assert ctl.active[2].fpe_capacity == 1024


def test_controller_carries_tree_shape():
    ctl = planner.Controller()
    t = tree_lib.worker_tree(16, 4)
    msg = ctl.configure(planner.LaunchRequest(9, 16, 1, 1), t)
    assert msg.fanins == (4, 4)
    assert msg.level_axes == ("lvl0", "lvl1")


# ---------------------------------------------------------------------------
# Planner.
# ---------------------------------------------------------------------------


def test_plan_grad_exchange_single_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = planner.plan_grad_exchange(mesh, mode=GradAggMode.TREE,
                                      grad_bytes=1 << 20)
    assert plan.mode == GradAggMode.TREE
    assert plan.upper_axes == ()


def test_size_fpe_capacity_inverts_eq3():
    from repro.core import reduction_model as rm

    N, M = 5000, 100000
    for target in (0.05, 0.3, 0.6):
        c = planner.size_fpe_capacity(N, target, M)
        achieved = rm.reduction_ratio(M, N, c)
        assert achieved >= target - 1e-9
    # asking for more than the ideal bound -> hold all keys
    assert planner.size_fpe_capacity(N, 0.999, M) == N


# ---------------------------------------------------------------------------
# Straggler monitor (fault tolerance unit).
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(factor=3.0, decay=0.9, warmup=2)
    for i in range(5):
        assert not mon.observe(i, 1.0)
    assert mon.observe(5, 10.0)  # 10x the EWMA
    assert mon.events and mon.events[0][0] == 5
    # the straggler did not poison the EWMA
    assert mon.ewma == pytest.approx(1.0, rel=1e-6)
    assert not mon.observe(6, 1.1)


def test_straggler_monitor_warmup_tolerant():
    mon = StragglerMonitor(factor=2.0, warmup=3)
    assert not mon.observe(0, 1.0)
    assert not mon.observe(1, 5.0)  # within warmup: compile steps etc.
    assert not mon.observe(2, 1.0)


def test_straggler_monitor_adapts():
    mon = StragglerMonitor(factor=3.0, decay=0.5, warmup=1)
    mon.observe(0, 1.0)
    for i in range(1, 10):
        mon.observe(i, 2.0)  # workload legitimately slows
    assert mon.ewma == pytest.approx(2.0, rel=1e-2)
    assert not mon.observe(10, 5.0)  # 2.5x new EWMA: fine
