"""The traffic generator: the same queries for every seed, in an order
the seed draws; the same update batches for every run of a feed, and
the weights they give each epoch; and the spread arithmetic."""

import hashlib

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


FEED = {"interval_s": 0.25, "alpha": 0.005, "tau": 0.2, "feed_seed": 5}
W0 = np.random.default_rng(0).integers(10, 201, 1244).astype(np.float64)


def batches(feed):
    return [feed.batch(i) for i in range(len(feed))]


def test_a_mix_without_a_feed_draws_what_it_drew():
    a = traffic.Phase(2**31 + 11, "window", MIX, 676)
    assert pairs(a)[:6] == [(436, 480), (546, 285), (192, 674), (466, 450),
                            (630, 505), (121, 77)]
    digest = hashlib.sha256(a.s.tobytes() + a.t.tobytes()
                            + a.k.tobytes()).hexdigest()
    assert digest == ("402e9d80957e9894ec03c376178b297a"
                      "d4bcab4ef91c31bc771d4767d2ee06b5")
    b = traffic.Phase(2**31 + 11, "window", dict(MIX, updates=FEED), 676)
    assert pairs(a) == pairs(b)


def test_same_feed_seed_same_batches():
    a = traffic.Feed(5, "window", FEED, W0, 51)
    b = traffic.Feed(5, "window", FEED, W0, 51)
    assert len(a) == 204 and a.due(203) == pytest.approx(50.75)
    for (ea, wa), (eb, wb) in zip(batches(a), batches(b)):
        assert np.array_equal(ea, eb) and np.array_equal(wa, wb)
    c = traffic.Feed(6, "window", FEED, W0, 51)
    assert not all(np.array_equal(x[0], y[0])
                   for x, y in zip(batches(a), batches(c)))


def test_warmup_and_window_feeds_draw_apart():
    w = traffic.Feed(5, "warmup", FEED, W0, 10)
    a = traffic.Feed(5, "window", FEED, W0, 10)
    assert len(w) == len(a) == 40
    assert not any(np.array_equal(x[0], y[0])
                   for x, y in zip(batches(w), batches(a)))


def test_feed_weights_are_whole_and_within_tau():
    feed = traffic.Feed(2**31 + 5, "window", FEED, W0, 51)
    for eids, new_w in batches(feed):
        assert eids.shape == (6,) and len(set(eids.tolist())) == 6
        assert np.all(new_w == np.round(new_w)) and np.all(new_w >= 1)
        lo = np.maximum(1, np.round(W0[eids] * 0.8))
        hi = np.round(W0[eids] * 1.2)
        assert np.all(lo <= new_w) and np.all(new_w <= hi)


@pytest.mark.parametrize("updates", [
    dict(FEED, period=1.0),
    {k: v for k, v in FEED.items() if k != "alpha"},
], ids=["unknown-key", "missing-key"])
def test_feed_keys_are_checked(updates):
    with pytest.raises(ValueError):
        traffic.Feed(5, "window", updates, W0, 10)


def test_replayed_weights_last_write_wins():
    feed = traffic.Feed(9, "window", dict(FEED, alpha=0.05), W0, 20)
    out = traffic.epoch_weights(W0, batches(feed))
    assert len(out) == len(feed) + 1 and np.array_equal(out[0], W0)
    w = W0.copy()
    for e, (eids, new_w) in enumerate(batches(feed), start=1):
        for i, x in zip(eids.tolist(), new_w.tolist()):
            w[i] = x
        assert np.array_equal(out[e], w)
