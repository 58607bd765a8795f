"""The one traffic generator: a mix file's parameters and a seed in,
the list of queries a phase offers out.

Traffic is a closed loop of callers, each sending its next query the
moment its last one is answered, over pairs whose origin and destination
are drawn uniformly from the network's vertices, never equal.  A mix
(``traffic/<name>.json``) is data only:

    clients         concurrent callers
    k               paths per query
    pool_seed       the queries are drawn from this fixed seed, the same
                    for every run; the run's seed orders them
    order_block     the run's seed shuffles the queries within each
                    block of this many consecutive ones, so that every
                    run offers the same set of queries over the window,
                    in another order
    max_queries     queries a phase can offer: more than the clients can
                    finish, a cap and not a schedule
    warmup_seconds  the same traffic, from its own draw, offered before
                    the window opens
    drain_seconds   how long after the window an answer may still come
    updates         optional: a live travel-time feed offered beside the
                    queries (``Feed``); a mix without it offers none

The feed block holds exactly these keys:

    interval_s      seconds between batches, on the host's clock
    alpha           share of the network's roads a batch changes; a
                    batch changes max(1, round(alpha * roads)) distinct
                    roads
    tau             each changed road gets the weight
                    max(1, round(w0 * f)), f ~ U[1 - tau, 1 + tau]:
                    relative to the initial weights, so drift stays
                    bounded, and whole, so sums stay exact
    feed_seed       the batches are drawn from this fixed seed, the same
                    for every run (the run's seed with --pairs-from-seed)

Every random stream derives from a seed and a stream name, so warm-up
and window never share draws.
"""

from __future__ import annotations

import math
import zlib

import numpy as np

FEED_KEYS = {"interval_s", "alpha", "tau", "feed_seed"}


def stream(seed, name):
    """An independent generator for one named stream of one seed."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = [seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(name.encode())]
    return np.random.default_rng(np.random.SeedSequence(words))


def uniform_pairs(rng, n_vertices, count):
    """``count`` (s, t) pairs, uniform over ordered pairs with s != t."""
    s = rng.integers(0, n_vertices, count)
    t = rng.integers(0, n_vertices - 1, count)
    return s.astype(np.int64), (t + (t >= s)).astype(np.int64)


class Phase:
    """One phase's queries (warm-up or window), in the order offered:
    ``s``, ``t`` and ``k`` arrays."""

    def __init__(self, seed, name, mix, n_vertices, pool_seed=None):
        count = int(mix["max_queries"])
        pool = mix["pool_seed"] if pool_seed is None else pool_seed
        s, t = uniform_pairs(stream(pool, name + ".pairs"), n_vertices,
                             count)
        block = int(mix["order_block"])
        order = stream(seed, name + ".order")
        idx = np.concatenate([lo + order.permutation(min(block, count - lo))
                              for lo in range(0, count, block)])
        self.s, self.t = s[idx], t[idx]
        self.k = np.full(count, int(mix["k"]), dtype=np.int64)


class Feed:
    """One phase's weight-update batches, in the order offered: batch
    ``i`` is due ``i * interval_s`` after the phase opens, and
    ``batch(i)`` is its (road ids, new weights)."""

    def __init__(self, seed, name, updates, w0, seconds):
        unknown = set(updates) - FEED_KEYS
        missing = FEED_KEYS - set(updates)
        if unknown or missing:
            raise ValueError(f"feed keys: unknown {sorted(unknown)}, "
                             f"missing {sorted(missing)}")
        self.interval_s = float(updates["interval_s"])
        alpha, tau = float(updates["alpha"]), float(updates["tau"])
        if self.interval_s <= 0 or not 0 < alpha <= 1 or not 0 <= tau < 1:
            raise ValueError(f"feed out of range: {updates}")
        w0 = np.asarray(w0, dtype=np.float64)
        m = w0.shape[0]
        size = max(1, round(alpha * m))
        rng = stream(seed, name + ".updates")
        self.eids, self.new_w = [], []
        for _ in range(math.ceil(float(seconds) / self.interval_s)):
            e = np.sort(rng.choice(m, size, replace=False)).astype(np.int64)
            f = rng.uniform(1.0 - tau, 1.0 + tau, size)
            self.eids.append(e)
            self.new_w.append(np.maximum(1.0, np.round(w0[e] * f)))

    def __len__(self):
        return len(self.eids)

    def due(self, i):
        """Seconds after the phase opens at which batch ``i`` is due."""
        return i * self.interval_s

    def batch(self, i):
        return self.eids[i], self.new_w[i]


def epoch_weights(w0, batches):
    """The weights at every epoch: ``out[e]`` is ``w0`` with the first
    ``e`` of ``batches`` ((road ids, new weights), in the order offered)
    applied, the last write to a road winning."""
    w = np.array(w0, dtype=np.float64)
    out = [w.copy()]
    for eids, new_w in batches:
        w[eids] = new_w
        out.append(w.copy())
    return out
