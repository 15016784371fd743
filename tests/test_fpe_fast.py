"""Batched-block FPE fast path — deterministic coverage (DESIGN.md §8).

The fast path's contract is SEMANTIC equivalence with the scan oracle:
for any stream, block split, and registered AggOp, grouping (flush +
evictions) by key gives the exact input combine — while the eviction
*pattern* is free to differ.  This module checks that contract over
seeded sweeps, pins the resident-table invariants the closed form relies
on, and asserts the shape-stable streaming ingest compiles O(1) traces.
Hypothesis generalizations live in tests/test_fpe_fast_properties.py.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import build_off_cpu
from repro.core import aggops, dataplane, kvagg
from repro.core.dataplane import CascadePlan, LevelSpec

EMPTY = int(kvagg.EMPTY_KEY)


def _grouped(keys, values, op):
    """Grouped-combine of a carried-value stream -> {key: np value}."""
    c = kvagg.sorted_combine(jnp.asarray(keys), jnp.asarray(values), op=op)
    nu = int(c.n_unique)
    ks = np.asarray(c.unique_keys)[:nu]
    vs = np.asarray(c.combined_values)[:nu]
    return {int(k): vs[i] for i, k in enumerate(ks)}


def _assert_same_grouped(got, want, op):
    assert got.keys() == want.keys(), f"{op}: key set mismatch"
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"op={op} key={k}")


def _fast_stream_grouped(keys, carried, *, capacity, ways, op, n_blocks):
    """Run the fast path over n_blocks chunks of a persistent table and
    return the grouped-combine of (final flush + all evictions)."""
    tk = tv = None
    out_k = []
    out_v = []
    for ck, cv in zip(np.array_split(keys, n_blocks),
                      np.array_split(carried, n_blocks)):
        if ck.shape[0] == 0:
            continue
        res = kvagg.fpe_aggregate(
            jnp.asarray(ck), jnp.asarray(cv), capacity=capacity, ways=ways,
            op=op, exact_stream=False, table_keys=tk, table_values=tv)
        tk, tv = res.table_keys, res.table_values
        out_k.append(np.asarray(res.evict_keys))
        out_v.append(np.asarray(res.evict_values))
    return _grouped(np.concatenate([np.asarray(tk)] + out_k),
                    np.concatenate([np.asarray(tv)] + out_v), op)


def assert_table_invariants(table_keys, *, capacity, ways):
    """Bucketing, front-contiguity, and uniqueness of a resident table."""
    w = max(1, min(ways, capacity))
    nb = max(1, capacity // w)
    tk = np.asarray(table_keys).reshape(nb, w)
    nonempty = tk != EMPTY
    for b in range(nb):
        r_b = int(nonempty[b].sum())
        assert nonempty[b, :r_b].all() and not nonempty[b, r_b:].any(), \
            f"bucket {b} not front-contiguous: {tk[b]}"
        for k in tk[b, :r_b]:
            assert int(aggops.hash_key(jnp.int32(k), nb)) == b, \
                f"key {k} stored outside its bucket {b}"
    resident = tk[nonempty]
    assert len(set(resident.tolist())) == resident.shape[0], \
        "a key is resident twice"


@pytest.mark.parametrize("op", sorted(aggops.names()))
@pytest.mark.parametrize("capacity,ways,n_blocks", [
    (1, 1, 1), (4, 2, 2), (16, 4, 1), (16, 4, 3), (64, 4, 2),
])
def test_fast_path_equals_scan_grouped_combine(op, capacity, ways, n_blocks):
    """(flush + evictions) grouped by key: fast path == scan oracle, for
    every registered op, across block splits and table geometries."""
    r = np.random.default_rng(capacity * 7 + ways)
    n = 200
    keys = r.integers(0, 48, size=n).astype(np.int32)
    raw = r.integers(-8, 8, size=n).astype(np.float32)
    carried = np.asarray(aggops.get(op).prepare_values(jnp.asarray(raw)))
    scan = kvagg.fpe_aggregate(
        jnp.asarray(keys), jnp.asarray(carried), capacity=capacity,
        ways=ways, op=op, exact_stream=True)
    want = _grouped(np.concatenate([scan.table_keys, scan.evict_keys]),
                    np.concatenate([scan.table_values, scan.evict_values]),
                    op)
    got = _fast_stream_grouped(keys, carried, capacity=capacity, ways=ways,
                               op=op, n_blocks=n_blocks)
    _assert_same_grouped(got, want, op)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fast_path_table_invariants(seed):
    r = np.random.default_rng(seed)
    n = 100 + 50 * seed
    keys = jnp.asarray(r.integers(0, 20 + 30 * seed, size=n)
                       .astype(np.int32))
    vals = jnp.asarray(r.standard_normal(n).astype(np.float32))
    capacity, ways = [(1, 1), (8, 2), (64, 4), (16, 16)][seed]
    res = kvagg.fpe_aggregate(keys, vals, capacity=capacity, ways=ways,
                              op="sum", exact_stream=False)
    assert_table_invariants(res.table_keys, capacity=capacity, ways=ways)


@pytest.mark.parametrize("op", ["sum", "mean"])
def test_fast_path_padded_stream(op, rng):
    """EMPTY_KEY padding must be skipped without touching totals."""
    keys = rng.integers(0, 12, size=160).astype(np.int32)
    mask = rng.random(160) < 0.3
    keys = np.where(mask, EMPTY, keys).astype(np.int32)
    raw = rng.standard_normal(160).astype(np.float32)
    carried = np.asarray(aggops.get(op).prepare_values(jnp.asarray(raw)))
    res = kvagg.fpe_aggregate(jnp.asarray(keys), jnp.asarray(carried),
                              capacity=16, ways=4, op=op, exact_stream=False)
    got = _grouped(np.concatenate([res.table_keys, res.evict_keys]),
                   np.concatenate([res.table_values, res.evict_values]), op)
    want = _grouped(keys, carried, op)
    _assert_same_grouped(got, want, op)
    assert EMPTY not in got


def test_fast_path_all_padding():
    res = kvagg.fpe_aggregate(jnp.full((8,), EMPTY, jnp.int32),
                              jnp.zeros((8,), jnp.float32),
                              capacity=4, ways=2, op="sum",
                              exact_stream=False)
    assert np.all(np.asarray(res.table_keys) == EMPTY)
    assert np.all(np.asarray(res.evict_keys) == EMPTY)


def test_two_level_fast_path_exactness(rng):
    """two_level_aggregate(exact_stream=False) keeps the node invariant."""
    keys = jnp.asarray(rng.integers(0, 48, size=256).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal(256).astype(np.float32))
    res = kvagg.two_level_aggregate(keys, vals, capacity=16, ways=4,
                                    exact_stream=False)
    got = _grouped(res.out_keys, res.out_values, "sum")
    want = _grouped(keys, vals, "sum")
    _assert_same_grouped(got, want, "sum")
    assert int(res.n_in) == 256
    assert int(res.n_out) == int(np.sum(np.asarray(res.out_keys) != EMPTY))


@pytest.mark.parametrize("op", sorted(aggops.names()))
def test_cascade_fast_path_every_op(op, rng):
    """run_cascade(exact_stream=False) finalized output == exact combine
    for every registered op over a multi-level plan."""
    from conftest import dict_aggregate

    keys = jnp.asarray(rng.integers(0, 64, size=300).astype(np.int32))
    vals = jnp.asarray(rng.integers(-8, 8, size=300).astype(np.float32))
    plan = CascadePlan(op=op, levels=(LevelSpec(32, ways=4),
                                      LevelSpec(16, ways=2)))
    res = dataplane.run_cascade(keys, vals, plan, exact_stream=False)
    got = {int(k): float(v) for k, v in
           zip(np.asarray(res.keys), np.asarray(res.values)) if k != EMPTY}
    want = dict_aggregate(keys, vals, op=op)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-5)


def test_stream_fast_path_matches_monolithic(rng):
    """run_cascade_stream(exact_stream=False) over packets == run_cascade
    grouped result (multi-lane op to cover carried lanes end to end)."""
    keys = rng.integers(0, 40, size=400).astype(np.int32)
    vals = rng.standard_normal(400).astype(np.float32)
    plan = CascadePlan(op="mean", levels=(LevelSpec(16, ways=4),))
    batches = [(keys[i:i + 37], vals[i:i + 37])
               for i in range(0, 400, 37)]
    res = dataplane.run_cascade_stream(batches, plan, exact_stream=False)
    mono = dataplane.run_cascade(jnp.asarray(keys), jnp.asarray(vals), plan)
    got = {int(k): float(v) for k, v in
           zip(np.asarray(res.keys), np.asarray(res.values)) if k != EMPTY}
    want = {int(k): float(v) for k, v in
            zip(np.asarray(mono.keys), np.asarray(mono.values)) if k != EMPTY}
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)


def test_stream_ingest_is_shape_stable():
    """Varying packet lengths must reuse O(log max_len) compiled FPE
    traces (pow2 size buckets), not one trace per distinct length."""
    r = np.random.default_rng(3)
    lengths = sorted(set(r.integers(1, 200, size=50).tolist()))
    assert len(lengths) > 20  # the test only bites with many lengths
    batches = [(r.integers(0, 64, size=n).astype(np.int32),
                np.ones(n, np.float32)) for n in lengths]
    plan = CascadePlan(op="sum", levels=(LevelSpec(16, ways=4),))
    before = kvagg.fpe_aggregate._cache_size()
    res = dataplane.run_cascade_stream(batches, plan)
    grew = kvagg.fpe_aggregate._cache_size() - before
    # pow2 buckets for 1..200 with the MIN_PAD=8 floor: 8..256 -> 6 sizes,
    # +1 for the very first ingest (table_keys=None vs resumed signature)
    assert grew <= 7, f"{grew} FPE traces for {len(lengths)} packet lengths"
    assert int(res.n_in) == sum(lengths)


def test_sim_fast_path_delivers_same_totals():
    """The packet simulator with exact_stream=False delivers the same
    application table as the paper-faithful default."""
    from repro.net import sim, simulate

    r = np.random.default_rng(5)
    keys = r.integers(0, 64, size=256).astype(np.int32)
    vals = np.ones(256, np.float32)
    plan = CascadePlan(op="sum", levels=(LevelSpec(32, ways=4),
                                         LevelSpec(32, ways=4)))
    exact = simulate(sim.JobSpec(keys=keys, values=vals, fanins=(2, 2),
                                 plan=plan))
    fast = simulate(sim.JobSpec(keys=keys, values=vals, fanins=(2, 2),
                                plan=plan,
                                cfg=sim.NetConfig(exact_stream=False)))
    assert exact.delivered_table() == fast.delivered_table()
    assert fast.jct_s > 0


@pytest.mark.parametrize("n_buckets,n,fill", [
    (1, 1, 1.0), (7, 40, 0.5), (16, 300, 0.9), (64, 50, 0.0), (64, 33, 1.0),
])
def test_run_starts_match_search(n_buckets, n, fill):
    """Bucket run starts: count + prefix sum == a binary search, over
    sorted bucket ids in [0, n_buckets] (n_buckets marks a vacant slot)."""
    r = np.random.default_rng(n_buckets * 31 + n)
    b_d = np.sort(np.where(r.random(n) < fill,
                           r.integers(0, n_buckets, size=n), n_buckets))
    want = np.searchsorted(b_d, np.arange(n_buckets + 1), side="left")
    got = kvagg._run_starts(jnp.asarray(b_d.astype(np.int32)), n_buckets)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


def _fast_calls(fpe, keys, carried, *, resumed):
    """One fast-path call, or two that resume the first call's table."""
    chunks = (np.array_split(np.arange(keys.shape[0]), 2) if resumed
              else [np.arange(keys.shape[0])])
    tk = tv = None
    outs = []
    for idx in chunks:
        res = fpe(jnp.asarray(keys[idx]), jnp.asarray(carried[idx]),
                  table_keys=tk, table_values=tv)
        tk, tv = res.table_keys, res.table_values
        outs.append(res)
    return outs


@pytest.mark.parametrize("resumed", [False, True])
@pytest.mark.parametrize("op", ["sum", "max", "min", "mean"])
def test_fast_path_branches_bit_identical(op, resumed):
    """fpe_aggregate(exact_stream=False) as lowered for a TPU == as lowered
    for the CPU: tables and eviction streams bit for bit, on a fresh and a
    resumed table, with padding, negative keys and INT32_MAX."""
    r = np.random.default_rng(17)
    n = 300
    keys = r.integers(-60, 60, size=n).astype(np.int32)
    keys[r.random(n) < 0.25] = EMPTY
    keys[::23] = np.iinfo(np.int32).max
    raw = r.integers(-8, 8, size=n).astype(np.float32)
    carried = np.asarray(aggops.get(op).prepare_values(jnp.asarray(raw)))
    static = dict(capacity=32, ways=4, op=op, exact_stream=False)
    want = _fast_calls(functools.partial(kvagg.fpe_aggregate, **static),
                       keys, carried, resumed=resumed)
    got = _fast_calls(build_off_cpu(kvagg.fpe_aggregate, **static),
                      keys, carried, resumed=resumed)
    assert any(int(jnp.sum(w.evict_keys != EMPTY)) for w in want)
    for g, w in zip(got, want):
        for field, gf, wf in zip(w._fields, g, w):
            gf, wf = np.asarray(gf), np.asarray(wf)
            assert (gf.shape, gf.dtype) == (wf.shape, wf.dtype), field
            assert gf.tobytes() == wf.tobytes(), field


def test_sorted_combine_int32max_key_legal():
    """No sentinel remap: INT32_MAX stays a legal, distinct key."""
    imax = np.iinfo(np.int32).max
    keys = jnp.asarray([imax, imax, 5, EMPTY], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 0.0], jnp.float32)
    c = kvagg.sorted_combine(keys, vals)
    assert int(c.n_unique) == 2
    uk = np.asarray(c.unique_keys)
    assert uk[0] == 5 and uk[1] == imax
    np.testing.assert_allclose(np.asarray(c.combined_values)[:2], [3.0, 3.0])
