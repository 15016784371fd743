"""Multi-job congestion-aware controller (DESIGN.md §3).

Covers: memory partitioning (even + weighted), deterministic planning,
congestion-aware ordering search, SOAR-style byte-budget escalation to the
compressed exchange, and the scarce-link win over naive flat all-reduces.
"""

import dataclasses
import math

import pytest

from repro.core import planner as pl
from repro.core import tree as tree_lib
from repro.core.collectives import GradAggMode
from repro.launch.mesh import make_mesh

MiB = 1 << 20


def _req(i, *, grad_mb=256, key_variety=1000, pairs=10_000,
         mode=GradAggMode.TREE):
    return pl.LaunchRequest(job_id=i, n_workers=32, expected_pairs=pairs,
                            key_variety=key_variety, grad_bytes=grad_mb * MiB,
                            mode=mode)


def _sched(*, budget_mb=math.inf, pairs=1 << 20, policy="even"):
    budget = budget_mb * MiB if budget_mb != math.inf else math.inf
    topo = pl.Topology.production(scarce_budget_bytes=budget)
    return pl.JobScheduler(topo, combiner_budget_pairs=pairs,
                           partition_policy=policy)


# ---------------------------------------------------------------------------
# Memory partitioning (paper §4.2.2).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["even", "weighted"])
@pytest.mark.parametrize("n_jobs", [1, 2, 3, 5, 8])
def test_partitions_sum_within_budget(policy, n_jobs):
    budget = 1 << 16
    reqs = [_req(i, key_variety=100 * (i + 1)) for i in range(n_jobs)]
    caps = pl.partition_memory(budget, reqs, policy)
    assert set(caps) == {r.job_id for r in reqs}
    assert sum(caps.values()) <= budget
    assert all(c >= 1 for c in caps.values())


def test_partition_even_matches_paper():
    reqs = [_req(i) for i in range(4)]
    caps = pl.partition_memory(1 << 20, reqs, "even")
    assert set(caps.values()) == {(1 << 20) // 4}


def test_partition_weighted_favors_key_variety():
    reqs = [_req(0, key_variety=100), _req(1, key_variety=900)]
    caps = pl.partition_memory(1000, reqs, "weighted")
    assert caps[1] == 9 * caps[0]


def test_partition_tiny_budget_never_overflows():
    # the >=1 floor must not push the sum past a budget smaller than n_jobs
    reqs = [_req(i, key_variety=10 * (i + 1)) for i in range(8)]
    caps = pl.partition_memory(5, reqs, "weighted")
    assert sum(caps.values()) <= 8  # every job still gets >= 1 pair
    assert all(c >= 1 for c in caps.values())


def test_scheduler_repartitions_on_admit_and_release():
    s = _sched(pairs=1 << 10, policy="even")
    s.admit(_req(0))
    assert s.jobs[0].exchange.fpe_capacity == 1 << 10
    s.admit(_req(1))
    assert s.jobs[0].exchange.fpe_capacity == 1 << 9  # re-partitioned
    assert s.jobs[1].exchange.fpe_capacity == 1 << 9
    s.release(0)
    assert s.jobs[1].exchange.fpe_capacity == 1 << 10


# ---------------------------------------------------------------------------
# Determinism.
# ---------------------------------------------------------------------------


def test_plans_are_deterministic():
    reqs = [_req(i, grad_mb=256 >> (i % 3), key_variety=500 * (i + 1))
            for i in range(6)]
    r1 = _sched(budget_mb=128, policy="weighted").plan_all(list(reqs))
    r2 = _sched(budget_mb=128, policy="weighted").plan_all(list(reversed(reqs)))
    assert [j.exchange for j in r1.jobs] == [j.exchange for j in r2.jobs]
    assert r1.link_totals == r2.link_totals
    assert r1.total_scarce_bytes == r2.total_scarce_bytes


# ---------------------------------------------------------------------------
# Congestion-aware tree selection.
# ---------------------------------------------------------------------------


def test_single_job_picks_cheap_axis_first():
    s = _sched()
    jp = s.admit(_req(0))
    # leaf must be the fat ICI level; the scarce pod level reduces last,
    # seeing only the 1/16 shard
    assert jp.exchange.leaf_axis == "data"
    assert jp.exchange.upper_axes == ("pod",)
    assert jp.exchange.scarce_link_bytes == pytest.approx(
        2 * (2 - 1) / 2 * 256 * MiB / 16)


def test_scheduled_beats_flat_on_scarce_link():
    for n in (1, 2, 4, 8):
        s = _sched(budget_mb=128)
        rep = s.plan_all([_req(i, grad_mb=256 >> (i % 4)) for i in range(n)])
        assert rep.total_scarce_bytes < rep.baseline_flat_scarce_bytes
        assert rep.scarce_traffic_cut > 0.4


def test_congestion_term_balances_link_load():
    # with the ICI level already saturated by big tenants, a small job's
    # best placement can flip leaf order to the idle level — the max-drain
    # objective must never pick a WORSE drain time than naive cheap-first
    s = _sched()
    for i in range(3):
        s.admit(_req(i, grad_mb=512))
    naive = s.link_loads()
    fanins = (16, 2)
    lvl = pl.modeled_level_bytes(64 * MiB, fanins)
    naive_trial = {"data": naive["data"] + lvl[0], "pod": naive["pod"] + lvl[1]}
    naive_drain = max(naive_trial["data"] / 50e9, naive_trial["pod"] / 6.25e9)
    jp = s.admit(_req(3, grad_mb=64))
    assert s._drain_s(s.link_loads()) <= naive_drain + 1e-12
    assert not jp.over_budget


def test_byte_budget_escalates_to_compression():
    # budget fits exactly one dense tree job; the second must compress
    dense_scarce = 2 * (2 - 1) / 2 * 256 * MiB / 16  # 16 MiB
    s = _sched(budget_mb=dense_scarce * 1.5 / MiB)
    j0 = s.admit(_req(0))
    assert j0.exchange.mode == GradAggMode.TREE
    j1 = s.admit(_req(1))
    assert j1.exchange.mode == GradAggMode.TREE_COMPRESS
    assert not j1.over_budget
    assert j1.exchange.k_fraction <= 0.01
    assert s.report().total_scarce_bytes <= dense_scarce * 1.5 + 1e-6
    # escalated jobs still carry *something* across the pod level
    assert j1.exchange.scarce_link_bytes > 0


def test_compress_requested_job_still_walks_k_ladder():
    # a job that already asked for TREE_COMPRESS with a too-large k must be
    # admitted with a smaller k, not flagged over-budget.  Headroom above
    # the first dense job is less than the k=0.01 payload (0.32 MiB), so
    # the ladder must halve k at least once.
    dense_scarce = 2 * (2 - 1) / 2 * 256 * MiB / 16
    s = _sched(budget_mb=(dense_scarce + 0.2 * MiB) / MiB)
    s.admit(_req(0))  # dense job eats most of the budget
    jp = s.admit(_req(1, mode=GradAggMode.TREE_COMPRESS))
    assert jp.exchange.mode == GradAggMode.TREE_COMPRESS
    assert not jp.over_budget
    assert jp.exchange.k_fraction < 0.01


def test_impossible_budget_flags_over_budget():
    s = _sched(budget_mb=1e-9)
    jp = s.admit(_req(0))
    assert jp.over_budget
    assert jp.exchange.mode == GradAggMode.TREE_COMPRESS
    assert jp.exchange.k_fraction == s.min_k_fraction


def test_duplicate_job_id_rejected():
    s = _sched()
    s.admit(_req(0))
    with pytest.raises(ValueError):
        s.admit(_req(0))


# ---------------------------------------------------------------------------
# Level-byte model.
# ---------------------------------------------------------------------------


def test_modeled_level_bytes_matches_traffic_model():
    from repro.core.reduction_model import TreeTrafficModel

    g, fanins = 1 << 30, (16, 2)
    want = TreeTrafficModel(grad_bytes=g, fanins=fanins).tree_bytes_per_level()
    got = pl.modeled_level_bytes(g, fanins, mode=GradAggMode.TREE)
    assert list(got) == pytest.approx(want)


def test_modeled_level_bytes_flat_is_uniform():
    g = 1 << 30
    got = pl.modeled_level_bytes(g, (16, 2), mode=GradAggMode.FLAT)
    assert got[0] == got[1] == pytest.approx(2 * 31 / 32 * g)


def test_modeled_level_bytes_compress_shrinks_uppers_only():
    g, k = 1 << 30, 0.01
    dense = pl.modeled_level_bytes(g, (16, 2), mode=GradAggMode.TREE)
    comp = pl.modeled_level_bytes(g, (16, 2), mode=GradAggMode.TREE_COMPRESS,
                                  k_fraction=k)
    assert comp[0] == dense[0]  # leaf reduce-scatter stays exact
    assert comp[1] == pytest.approx(dense[1] * 2 * k)


# ---------------------------------------------------------------------------
# Topology construction and report plumbing.
# ---------------------------------------------------------------------------


def test_topology_from_mesh_skips_absent_axes():
    import jax

    mesh = make_mesh((1, 1), ("data", "model"))
    topo = pl.Topology.from_mesh(mesh)
    assert len(topo.links) == 1  # degenerate but total


def test_topology_scarce_axis_is_slowest():
    topo = pl.Topology.production()
    assert topo.scarce_axis == "pod"
    assert topo.link("pod").gbps < topo.link("data").gbps


def test_report_summary_mentions_every_job():
    s = _sched(budget_mb=128)
    rep = s.plan_all([_req(i) for i in range(3)])
    text = rep.summary()
    for i in range(3):
        assert f"job {i}:" in text


def test_plan_grad_exchange_reports_level_bytes():
    import jax

    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    plan = pl.plan_grad_exchange(mesh, grad_bytes=64 * MiB,
                                 reduce_axes=("data", "model"))
    if plan.fanins and math.prod(plan.fanins) > 1:
        assert len(plan.level_bytes) == len(plan.fanins)
        assert plan.scarce_link_bytes > 0


def test_exchange_plan_describe_is_stable():
    plan = pl.ExchangePlan(
        mode=GradAggMode.TREE, leaf_axis="data", upper_axes=("pod",),
        k_fraction=0.01, fpe_capacity=64, predicted_root_reduction=0.9,
        predicted_kv_reduction=0.5, job_id=7, fanins=(16, 2),
        level_bytes=(1.0, 2.0), scarce_link_bytes=2.0 * MiB)
    assert "job 7" in plan.describe()
    assert "data(x16) -> pod(x2)" in plan.describe()


def test_tree_for_preserves_ordering():
    topo = pl.Topology.production()
    t = topo.tree_for(tuple(reversed(topo.links)))
    assert t.axes == ("pod", "data")
    assert isinstance(t, tree_lib.AggregationTree)


def test_exchange_from_plan_drives_dataplane():
    # the plan (not hardcoded args) selects the exchange; on the degenerate
    # single-device mesh the tree exchange must be the identity sum
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as coll

    mesh = make_mesh((1, 1), ("data", "model"))
    plan = pl.plan_grad_exchange(mesh, reduce_axes=("data", "model"))
    assert plan.mode == GradAggMode.TREE and plan.upper_axes == ()

    def region(g):
        out, _ = coll.exchange_from_plan(g, plan)
        return out

    mapped = jax.shard_map(
        region, mesh=mesh, in_specs=(P(),), out_specs=P(),
        axis_names={"data", "model"}, check_vma=False)
    x = {"w": jnp.arange(8.0)}
    out = jax.jit(mapped)(x)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(8.0))
