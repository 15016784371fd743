"""The stream generators: TPC-H lineitem rows and the word streams
(Zipf and uniform)."""

import json
import pathlib

import numpy as np
import pytest

from chipbench.harness import load_module, prng_key

BASE = pathlib.Path(__file__).resolve().parents[1]
TPCH = load_module(BASE / "data" / "tpch_lineitem.py")
ZIPF = load_module(BASE / "data" / "words.py")
WORDCOUNT = json.loads((BASE / "configs" / "hibench-wordcount.json").read_text())
Q18 = json.loads((BASE / "configs" / "tpch-q18.json").read_text())
BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def lineitem():
    return TPCH.generate(Q18, BIG_SEED, None)


def test_q18_rows_per_order(lineitem):
    keys, _ = lineitem
    _, per_order = np.unique(keys, return_counts=True)
    assert per_order.shape[0] == 1_500_000
    assert per_order.min() == 1 and per_order.max() == 7
    assert 3.99 < per_order.mean() < 4.01  # uniform 1..7
    assert 5_985_000 < keys.shape[0] < 6_015_000


def test_q18_keys_sparse_as_dbgen_makes_them(lineitem):
    keys, _ = lineitem
    uniq = np.unique(keys)
    assert uniq[0] == 1 and uniq[-1] == 6_000_000
    assert np.all((uniq >> 3) & 3 == 0)  # 2 zero bits above the low 3
    blocks = np.bincount(uniq >> 5)
    assert set(blocks[1:-1].tolist()) == {8}  # 8 keys used in every 32
    assert keys.dtype == np.int32


def test_q18_lineitems_of_an_order_are_adjacent(lineitem):
    keys, qty = lineitem
    starts = np.flatnonzero(np.diff(keys)) + 1
    assert starts.shape[0] + 1 == 1_500_000  # one run per order
    assert np.all(np.diff(keys) >= 0)
    assert qty.min() == 1 and qty.max() == 50
    assert np.all(qty == np.round(qty)) and qty.dtype == np.float32


def test_q18_same_seed_same_rows():
    small = dict(Q18, orders_per_scale=1000)
    a, b, c = (TPCH.generate(small, s, None) for s in (5, 5, 6))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])


def _zipf(seed, records=1 << 16, vocabulary=1 << 12, skew=0.99):
    config = {"records": records, "vocabulary": vocabulary, "skew": skew}
    keys, values = ZIPF.generate(config, seed, prng_key(seed))
    return np.asarray(keys), np.asarray(values)


def test_zipf_range_and_values():
    keys, values = _zipf(BIG_SEED)
    assert keys.dtype == np.int32 and keys.shape == (1 << 16,)
    assert keys.min() >= 0 and keys.max() < 1 << 12
    assert np.all(values == 1.0)


def test_zipf_seed_determinism():
    a, b, c = _zipf(BIG_SEED)[0], _zipf(BIG_SEED)[0], _zipf(BIG_SEED + 1)[0]
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # seeds wider than 32 bits are not folded onto small ones
    assert not np.array_equal(_zipf(2**32 + 3)[0], _zipf(3)[0])


def test_zipf_follows_its_law():
    keys, _ = _zipf(11, records=1 << 20)
    counts = np.bincount(keys, minlength=1 << 12).astype(float)
    assert counts.argmax() == 0
    p = np.arange(1, (1 << 12) + 1, dtype=float) ** -0.99
    want = p / p.sum() * keys.shape[0]
    top = slice(0, 64)  # frequent ranks: tight against the law
    assert np.max(np.abs(counts[top] - want[top]) / want[top]) < 0.05
    assert abs(counts.sum() - want.sum()) == 0


def test_zipf_cdf_steps_cover_the_draws():
    steps = ZIPF.cdf_steps(1 << 10, 0.99)
    assert steps.dtype == np.uint32 and np.all(np.diff(steps) > 0)
    assert steps[-1] == 2**32 - 1


def test_uniform_words_as_randomtextwriter_draws_them():
    vocab = WORDCOUNT["vocabulary"]
    assert vocab == 1000 and WORDCOUNT["skew"] == 0.0
    keys, values = _zipf(BIG_SEED, records=1 << 20, vocabulary=vocab,
                         skew=0.0)
    counts = np.bincount(keys, minlength=vocab)
    assert counts.shape[0] == vocab and counts.min() > 0
    want = keys.shape[0] / vocab
    # every word alike: counts within 6 standard deviations of the mean
    assert np.max(np.abs(counts - want)) < 6 * np.sqrt(want)
    assert np.all(values == 1.0)
    steps = ZIPF.cdf_steps(vocab, 0.0)
    assert np.max(np.abs(np.diff(steps.astype(np.int64)) - 2**32 / vocab)) \
        <= 1
