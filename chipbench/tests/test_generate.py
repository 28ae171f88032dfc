"""The traffic generator: the same seed gives the same inputs, and the
ids follow the mix's Zipf law."""
import numpy as np

from chipbench import generate

MIX = {"batch": 2, "seq": 4096, "alpha": 1.1, "pool": 3}


def test_same_seed_same_batches():
    big = 2**31 + 12345
    a = generate.batches(10_000, MIX, big)
    b = generate.batches(10_000, MIX, big)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_seeds_differ_in_high_bits():
    a = generate.batches(10_000, MIX, 7)
    b = generate.batches(10_000, MIX, 7 + 2**32)
    assert not np.array_equal(a[0]["tokens"], b[0]["tokens"])


def test_pool_batches_are_distinct_and_in_range():
    pool = generate.batches(10_000, MIX, 3)
    assert len(pool) == 3
    assert not np.array_equal(pool[0]["tokens"], pool[1]["tokens"])
    for b in pool:
        assert b["tokens"].dtype == np.int32
        assert b["tokens"].shape == (2, 4096)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 10_000


def test_zipf_shape():
    mix = dict(MIX, batch=16, pool=1)
    ids = generate.batches(50_000, mix, 11)[0]["tokens"].reshape(-1)
    counts = np.sort(np.bincount(ids, minlength=50_000))[::-1]
    # rank-frequency slope on the head follows -alpha
    ranks = np.arange(1, 51)
    slope = np.polyfit(np.log(ranks), np.log(counts[:50]), 1)[0]
    assert abs(slope + 1.1) < 0.15
    # the most frequent id takes its Zipf share of the draws
    p1 = 1.0 / np.sum(np.arange(1, 50_001, dtype=np.float64) ** -1.1)
    assert abs(counts[0] / ids.size - p1) < 0.15 * p1
