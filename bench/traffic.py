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

Every random stream derives from a seed and a stream name, so warm-up
and window never share draws.
"""

from __future__ import annotations

import zlib

import numpy as np


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
