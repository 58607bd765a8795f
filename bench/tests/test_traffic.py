"""The traffic generator: the same queries for every seed, in an order
the seed draws, and the spread arithmetic."""

import numpy as np
import pytest

import stats
import traffic

MIX = {"clients": 16, "k": 3, "pool_seed": 3, "order_block": 16,
       "max_queries": 200}


def pairs(ph):
    return list(zip(ph.s.tolist(), ph.t.tolist()))


def test_same_seed_same_queries_in_the_same_order():
    a = traffic.Phase(2**31 + 11, "window", MIX, 676)
    b = traffic.Phase(2**31 + 11, "window", MIX, 676)
    assert pairs(a) == pairs(b) and np.array_equal(a.k, b.k)
    assert len(a.s) == 200 and set(a.k.tolist()) == {3}
    assert np.all(a.s != a.t) and a.s.max() < 676 and a.t.max() < 676


def test_every_seed_offers_the_same_blocks_in_another_order():
    a = traffic.Phase(3000000001, "window", MIX, 676)
    b = traffic.Phase(3000000002, "window", MIX, 676)
    assert pairs(a) != pairs(b)
    for lo in range(0, 200, 16):
        assert sorted(pairs(a)[lo:lo + 16]) == sorted(pairs(b)[lo:lo + 16])


def test_warmup_draws_its_own_queries():
    a = traffic.Phase(7, "window", MIX, 676)
    w = traffic.Phase(7, "warmup", MIX, 676)
    assert not set(pairs(a)) >= set(pairs(w))


def test_pairs_from_the_run_seed():
    a = traffic.Phase(11, "window", MIX, 676, pool_seed=11)
    b = traffic.Phase(12, "window", MIX, 676, pool_seed=12)
    assert sorted(pairs(a)) != sorted(pairs(b))


def test_uniform_pairs_cover_every_ordered_pair():
    s, t = traffic.uniform_pairs(np.random.default_rng(0), 4, 4000)
    assert np.all(s != t)
    seen = set(zip(s.tolist(), t.tolist()))
    assert seen == {(a, b) for a in range(4) for b in range(4) if a != b}


def test_spread_uses_statistics_quartiles():
    med, q1, q3, rel = stats.spread([10, 11, 12, 13, 14, 15])
    assert med == 12.5
    assert (q1, q3) == (10.75, 14.25)
    assert rel == pytest.approx(3.5 / 12.5)


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        traffic.stream(-1, "graph")
