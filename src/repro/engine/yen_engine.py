"""Engine-level KSP: Yen's loopless outer loop, device-batched inner loop.

The host drives Yen's deviation paradigm; every iteration's spur searches
(one per deviation vertex) become ONE masked batched Bellman–Ford call —
PYen's "parallel deviation path identification" with SIMD instead of
threads.  PYen's A_D/A_P reuse appears as warm-start initialization, and
its early termination as the distance-cap clamp (both inside bf_solve).

Exactness: identical to core.yen (tested); the batching changes schedule,
not math.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .dense import (
    INF,
    bf_parents,
    bf_parents_grouped,
    bf_solve,
    bf_solve_grouped,
)

_INF = float(INF)


def _extract(parent_row, src, dst):
    path = [dst]
    v = dst
    hops = 0
    while v != src:
        v = int(parent_row[v])
        if v < 0 or hops > parent_row.shape[0]:
            return None
        path.append(v)
        hops += 1
    return path[::-1]


@functools.lru_cache(maxsize=None)
def _jit_solver(P, z):
    """Shape-bucketed jitted (solve + parents): P is padded to powers of
    two so Yen's varying deviation counts never re-trigger compilation."""

    @jax.jit
    def run(adj2d, init, bv, so, bn, cap):
        adj = jnp.broadcast_to(adj2d[None], (P, z, z))
        dist, _ = bf_solve(adj, init, bv, so, bn, cap=cap)
        parent = bf_parents(adj, dist, so, bn)
        return dist, parent

    return run


# (init, bv) of a grouped solve's (adj, init, bv, so, bn, cap): the two
# round buffers XLA can reuse
DONATED_ARGS = (1, 2)


@functools.lru_cache(maxsize=None)
def grouped_solver(S, J, z, donate: bool = False):
    """Shape-bucketed jitted grouped (solve + parents) over the
    owner-aligned [S, J, z] slab layout: J spur problems per subgraph
    relaxed against adj [S, z, z] with zero gather.  The distributed
    dense worker path (repro.dist.grouped_yen) dispatches through this;
    callers bucket S and J so varying batch shapes reuse compilations.

    ``donate=True`` donates the per-round ``init`` and ``bv`` buffers
    via ``donate_argnums``, so on device backends XLA reuses their
    memory (``init`` becomes the ``dist`` output) instead of allocating
    fresh — the recopy-avoidance half of the async pipeline.  The other
    masks and the caps have no output of their dtype and shape to take,
    and donating them only makes XLA warn on every bucket compile.
    Callers must only donate buffers packed fresh for the round
    (``SlabLayout.pack_round`` guarantees this); donation is a no-op on
    CPU, where backends leave it off.
    """

    def run(adj, init, bv, so, bn, cap):
        dist, _ = bf_solve_grouped(adj, init, bv, so, bn, cap=cap)
        parent = bf_parents_grouped(adj, dist, so, bn)
        return dist, parent

    if donate:
        return jax.jit(run, donate_argnums=DONATED_ARGS)
    return jax.jit(run)


def _spur_batch(adj_np, jobs, warm=None, caps=None):
    """jobs: list of (spur, banned_v bool[z], banned_next bool[z]).
    Returns (dist [P,z] np, parent [P,z] np)."""
    P = len(jobs)
    z = adj_np.shape[0]
    P_pad = 1 << (P - 1).bit_length() if P > 1 else 1
    init = np.full((P_pad, z), _INF, np.float32)
    bv = np.zeros((P_pad, z), bool)
    so = np.zeros((P_pad, z), bool)
    bn = np.zeros((P_pad, z), bool)
    cap = np.full(P_pad, _INF, np.float32)
    for i, (spur, banned_v, banned_next) in enumerate(jobs):
        init[i, spur] = 0.0
        bv[i] = banned_v
        so[i, spur] = True
        bn[i] = banned_next
        if warm is not None and warm[i] is not None:
            init[i] = np.minimum(init[i], warm[i])
    if caps is not None:
        cap[:P] = caps
    # padding rows have all-INF init -> relaxation no-ops on them
    dist, parent = _jit_solver(P_pad, z)(
        jnp.asarray(adj_np), jnp.asarray(init), jnp.asarray(bv),
        jnp.asarray(so), jnp.asarray(bn), jnp.asarray(cap),
    )
    return np.asarray(dist)[:P], np.asarray(parent)[:P]


def engine_ksp(adj_np: np.ndarray, src: int, dst: int, k: int,
               use_cap: bool = True):
    """K shortest simple paths on a dense adjacency via batched BF.

    adj_np: float32[z,z] min-plus adjacency (INF off-edges, 0 diagonal).
    Returns [(dist, path-tuple)], ascending."""
    z = adj_np.shape[0]
    # P1 by a single-problem solve
    dist, parent = _spur_batch(adj_np, [(src, np.zeros(z, bool), np.zeros(z, bool))])
    if dist[0, dst] >= _INF / 2:
        return []
    p1 = _extract(parent[0], src, dst)
    found = [(float(dist[0, dst]), tuple(p1))]
    found_set = {tuple(p1)}
    cand: list = []
    cand_set: set = set()

    while len(found) < k:
        prev_dist, prev = found[-1]
        # prefix distances along prev
        pre = [0.0]
        for a, b in zip(prev, prev[1:]):
            pre.append(pre[-1] + float(adj_np[a, b]))
        jobs, meta, caps = [], [], []
        for l in range(len(prev) - 1):
            spur = prev[l]
            root = prev[: l + 1]
            banned_next = np.zeros(z, bool)
            for fd, fp in found:
                if len(fp) > l and fp[: l + 1] == root:
                    banned_next[fp[l + 1]] = True
            banned_v = np.zeros(z, bool)
            for v in root[:-1]:
                banned_v[v] = True
            cap = _INF
            if use_cap:
                need = k - len(found)
                if len(cand) >= need:
                    cap = cand[need - 1][0] - pre[l] + 1e-9
            jobs.append((spur, banned_v, banned_next))
            meta.append((l, spur))
            caps.append(cap)
        dist, parent = _spur_batch(adj_np, jobs, caps=np.array(caps))
        for i, (l, spur) in enumerate(meta):
            if dist[i, dst] >= _INF / 2:
                continue
            tail = _extract(parent[i], spur, dst)
            if tail is None:
                continue
            full = tuple(prev[:l]) + tuple(tail)
            if full in found_set or full in cand_set:
                continue
            if len(set(full)) != len(full):
                continue
            cand_set.add(full)
            cand.append((pre[l] + float(dist[i, dst]), full))
        if not cand:
            break
        cand.sort(key=lambda x: (x[0], x[1]))
        best = cand.pop(0)
        cand_set.discard(best[1])
        found.append(best)
        found_set.add(best[1])
    return found
