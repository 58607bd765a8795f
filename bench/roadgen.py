"""The benchmark's own road networks.

A copy of the synthetic grid generator that the program ships, kept
here so that a later change to the program cannot move the yardstick.
It returns plain arrays; ``run.py`` hands them to the program as its
graph type.

Grid: a rows x cols lattice of intersections with 8% of the lattice
edges knocked out (rivers, parks), 3% extra short diagonal shortcuts
(highways), parallel edges merged, integer travel times drawn uniformly
from [w_low, w_high], restricted to the largest connected component.
"""

from __future__ import annotations

from collections import deque

import numpy as np


def grid_network(rng, rows, cols, *, knockout=0.08, shortcut_frac=0.03,
                 w_low=1, w_high=20):
    """(n, edge_u, edge_v, w0) of one seeded road-like grid."""
    n = rows * cols
    us, vs = [], []
    for r in range(rows):
        for c in range(cols):
            v = r * cols + c
            if c + 1 < cols:
                us.append(v)
                vs.append(v + 1)
            if r + 1 < rows:
                us.append(v)
                vs.append(v + cols)
    us = np.array(us, dtype=np.int64)
    vs = np.array(vs, dtype=np.int64)
    keep = rng.random(us.shape[0]) >= knockout
    us, vs = us[keep], vs[keep]

    n_short = int(shortcut_frac * us.shape[0])
    if n_short:
        su = rng.integers(0, n, n_short)
        dr = rng.integers(1, 4, n_short)
        dc = rng.integers(1, 4, n_short)
        sv = np.minimum(n - 1, su + dr * cols + dc)
        ok = sv != su
        us = np.concatenate([us, su[ok]])
        vs = np.concatenate([vs, sv[ok]])

    lo, hi = np.minimum(us, vs), np.maximum(us, vs)
    _, idx = np.unique(lo * n + hi, return_index=True)
    us, vs = us[idx], vs[idx]
    w0 = rng.integers(w_low, w_high + 1, us.shape[0]).astype(np.float64)
    return _largest_component(n, us, vs, w0)


def _largest_component(n, us, vs, w0):
    adj = [[] for _ in range(n)]
    for a, b in zip(us.tolist(), vs.tolist()):
        adj[a].append(b)
        adj[b].append(a)
    comp = np.full(n, -1, dtype=np.int64)
    cid = 0
    for s in range(n):
        if comp[s] >= 0:
            continue
        comp[s] = cid
        q = deque([s])
        while q:
            u = q.popleft()
            for v in adj[u]:
                if comp[v] < 0:
                    comp[v] = cid
                    q.append(v)
        cid += 1
    if cid == 1:
        return n, us, vs, w0
    big = int(np.argmax(np.bincount(comp)))
    keep_v = np.nonzero(comp == big)[0]
    remap = np.full(n, -1, dtype=np.int64)
    remap[keep_v] = np.arange(keep_v.shape[0])
    mask = (comp[us] == big) & (comp[vs] == big)
    return int(keep_v.shape[0]), remap[us[mask]], remap[vs[mask]], w0[mask]

