"""core.kvagg — the pure-jnp SwitchAgg node (FPE scan + BPE sorted combine)."""

import importlib.util
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="dev-only dep: pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from conftest import build_off_cpu, dict_aggregate
from repro.core import aggops, kvagg

EMPTY = int(kvagg.EMPTY_KEY)
IMAX = int(np.iinfo(np.int32).max)


# --------------------------------------------------------------------------
# sorted_combine (the BPE / vectorized exact aggregator)
# --------------------------------------------------------------------------


def test_sorted_combine_exact(rng):
    keys = jnp.asarray(rng.integers(0, 20, size=100).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal(100).astype(np.float32))
    res = kvagg.sorted_combine(keys, vals)
    got = dict_aggregate(res.unique_keys, res.combined_values)
    want = dict_aggregate(keys, vals)
    assert got.keys() == want.keys()
    for k in want:
        # atol: near-cancelling fp32 sums reassociate under segment_sum
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)
    assert int(res.n_unique) == len(want)
    # packed ascending with EMPTY padding after n_unique
    uk = np.asarray(res.unique_keys)
    nu = int(res.n_unique)
    assert np.all(np.diff(uk[:nu]) > 0)
    assert np.all(uk[nu:] == EMPTY)


def test_sorted_combine_all_padding():
    keys = jnp.full((16,), EMPTY, jnp.int32)
    vals = jnp.zeros((16,), jnp.float32)
    res = kvagg.sorted_combine(keys, vals)
    assert int(res.n_unique) == 0
    assert np.all(np.asarray(res.unique_keys) == EMPTY)


def test_sorted_combine_single_key():
    keys = jnp.zeros((8,), jnp.int32)
    vals = jnp.ones((8,), jnp.float32)
    res = kvagg.sorted_combine(keys, vals)
    assert int(res.n_unique) == 1
    assert float(res.combined_values[0]) == 8.0


@pytest.mark.parametrize("op", ["max", "min"])
def test_sorted_combine_ops(op, rng):
    keys = jnp.asarray(rng.integers(0, 5, size=64).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    res = kvagg.sorted_combine(keys, vals, op=op)
    want = dict_aggregate(keys, vals, op=op)
    got = dict_aggregate(res.unique_keys, res.combined_values, op=op)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6)


def _edge_stream(case):
    """(keys, integer-valued raw values) for the group-map equivalence
    cases: EMPTY padding, negative keys and INT32_MAX ("mixed"), n = 0,
    n = 1 and a stream of padding only."""
    r = np.random.default_rng(11)
    if case == "n0":
        keys = np.zeros((0,), np.int32)
    elif case == "n1":
        keys = np.asarray([IMAX], np.int32)
    elif case == "all_empty":
        keys = np.full((33,), EMPTY, np.int32)
    else:
        keys = r.integers(-40, 40, size=257).astype(np.int32)
        keys[r.random(257) < 0.3] = EMPTY
        keys[::17] = IMAX
    raw = r.integers(-8, 8, size=keys.shape[0]).astype(np.float32)
    return jnp.asarray(keys), raw


def assert_bit_identical(got, want):
    """Every field of two results: same shape, dtype and bytes."""
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.shape, g.dtype) == (w.shape, w.dtype), f"field {i}"
        assert g.tobytes() == w.tobytes(), f"field {i}: {g} != {w}"


EDGE_CASES = ["mixed", "n0", "n1", "all_empty"]
BRANCH_OPS = ["sum", "max", "min", "mean", "count", "logsumexp"]


def assert_same_totals(op, got, want):
    """Bit for bit, except logsumexp, whose exp and log may round apart
    when the branches add in another order."""
    if op == "logsumexp":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
    else:
        assert_bit_identical([got], [want])


@pytest.mark.parametrize("case", EDGE_CASES)
@pytest.mark.parametrize("op", BRANCH_OPS)
def test_group_reduce_branches_agree(op, case):
    """The TPU group map (one sort carrying the values, run starts by a
    running max) gives the CPU search's sorted keys, first-occurrence mask
    and, at every first occurrence, the same combined value."""
    keys, raw = _edge_stream(case)
    carried = aggops.get(op).prepare_values(jnp.asarray(raw))
    ks_s, first_s, comb_s = kvagg._group_reduce_searched(keys, carried, op=op)
    ks_c, first_c, comb_c = kvagg._group_reduce_sorted(keys, carried, op=op)
    assert_bit_identical((ks_c, first_c), (ks_s, first_s))
    first = np.asarray(first_s)
    assert_same_totals(op, np.asarray(comb_c)[first],
                       np.asarray(comb_s)[first])


@pytest.mark.parametrize("case", EDGE_CASES)
@pytest.mark.parametrize("op", BRANCH_OPS)
def test_sorted_combine_branches_bit_identical(op, case):
    """sorted_combine as lowered for a TPU == as lowered for the CPU, bit
    for bit, on integer-valued inputs (logsumexp's totals to rounding)."""
    keys, raw = _edge_stream(case)
    carried = aggops.get(op).prepare_values(jnp.asarray(raw))
    want = kvagg.sorted_combine(keys, carried, op=op)
    got = build_off_cpu(kvagg.sorted_combine, op=op)(keys, carried)
    assert_bit_identical((got.unique_keys, got.n_unique),
                         (want.unique_keys, want.n_unique))
    assert_same_totals(op, got.combined_values, want.combined_values)


def test_group_map_bench_times_both_branches():
    """benchmarks/bench_group_map.py, the split's reason as a script: one
    row per branch, the branches' groups checked equal inside."""
    spec = importlib.util.spec_from_file_location(
        "bench_group_map", pathlib.Path(__file__).resolve().parents[1]
        / "benchmarks" / "bench_group_map.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rows = bench.main(["--sizes", "1000,1", "--reps", "1"])
    assert [(r["n"], r["branch"]) for r in rows] == [
        (1000, "searched"), (1000, "sorted"), (1, "searched"), (1, "sorted")]
    assert all(r["ms"] > 0 for r in rows)


# --------------------------------------------------------------------------
# fpe_aggregate (paper-faithful hash engine) + two_level node
# --------------------------------------------------------------------------


def test_fpe_no_evictions_when_capacity_sufficient(rng):
    """Distinct keys <= direct capacity/ways buckets -> depends on hashing;
    use variety=1 which always fits."""
    keys = jnp.zeros((32,), jnp.int32)
    vals = jnp.ones((32,), jnp.float32)
    r = kvagg.fpe_aggregate(keys, vals, capacity=8, ways=4)
    assert np.all(np.asarray(r.evict_keys) == EMPTY)
    got = dict_aggregate(r.table_keys, r.table_values)
    assert got == {0: 32.0}


def test_fpe_eviction_forwards_resident_pair():
    """Force a collision: ways=1, two keys hashing to the same bucket."""
    # with n_buckets=1 every key collides
    keys = jnp.asarray([5, 9, 5], dtype=jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 4.0], dtype=jnp.float32)
    r = kvagg.fpe_aggregate(keys, vals, capacity=1, ways=1)
    ek = np.asarray(r.evict_keys)
    ev = np.asarray(r.evict_values)
    # key 5 inserted; 9 evicts 5; 5 evicts 9
    np.testing.assert_array_equal(ek, [EMPTY, 5, 9])
    np.testing.assert_allclose(ev, [0.0, 1.0, 2.0])
    assert np.asarray(r.table_keys)[0] == 5
    assert np.asarray(r.table_values)[0] == 4.0


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 300),
    variety=st.integers(1, 100),
    capacity=st.sampled_from([1, 4, 16, 128]),
    ways=st.sampled_from([1, 2, 4, 8]),
    op=st.sampled_from(["sum", "max", "min"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_two_level_exactness(n, variety, capacity, ways, op, seed):
    """two_level_aggregate(bpe=True) == exact group-by-key for any stream."""
    r = np.random.default_rng(seed)
    keys = jnp.asarray(r.integers(0, variety, size=n).astype(np.int32))
    vals = jnp.asarray(r.integers(-16, 16, size=n).astype(np.float32))
    res = kvagg.two_level_aggregate(keys, vals, capacity=capacity, ways=ways, op=op)
    got = dict_aggregate(res.out_keys, res.out_values, op=op)
    want = dict_aggregate(keys, vals, op=op)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)
    assert int(res.n_in) == n


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_bpe_improves_reduction(seed):
    """M-* >= S-* (paper Fig. 9): BPE combine can only reduce output pairs."""
    r = np.random.default_rng(seed)
    keys = jnp.asarray(r.integers(0, 64, size=256).astype(np.int32))
    vals = jnp.asarray(r.standard_normal(256).astype(np.float32))
    with_bpe = kvagg.two_level_aggregate(keys, vals, capacity=16, ways=4, bpe=True)
    without = kvagg.two_level_aggregate(keys, vals, capacity=16, ways=4, bpe=False)
    assert int(with_bpe.n_out) <= int(without.n_out)
    rr_with = float(kvagg.reduction_ratio(with_bpe))
    rr_without = float(kvagg.reduction_ratio(without))
    assert rr_with >= rr_without


def test_reduction_ratio_skewed_beats_uniform(rng):
    """Paper Fig. 9: Zipf hot keys aggregate in the FPE -> higher ratio."""
    n = 1024
    zipf = np.minimum(rng.zipf(1.5, size=n), 1000).astype(np.int32) - 1
    unif = rng.integers(0, 1000, size=n).astype(np.int32)
    vals = jnp.ones((n,), jnp.float32)
    r_z = kvagg.two_level_aggregate(jnp.asarray(zipf), vals, capacity=64, ways=4, bpe=False)
    r_u = kvagg.two_level_aggregate(jnp.asarray(unif), vals, capacity=64, ways=4, bpe=False)
    assert float(kvagg.reduction_ratio(r_z)) > float(kvagg.reduction_ratio(r_u))


# --------------------------------------------------------------------------
# payload analyzer (length grouping)
# --------------------------------------------------------------------------


def test_length_group_paper_bins():
    """Paper §5: keys 8..64 B in 8 groups of base 8."""
    lens = jnp.asarray([1, 8, 9, 16, 17, 33, 64, 200], jnp.int32)
    g = np.asarray(kvagg.length_group(lens, base=8, n_groups=8))
    np.testing.assert_array_equal(g, [0, 0, 1, 1, 2, 4, 7, 7])


def test_hash_key_range():
    keys = jnp.arange(-5, 1000, dtype=jnp.int32)
    h = np.asarray(kvagg.hash_key(keys, 17))
    assert h.min() >= 0 and h.max() < 17
