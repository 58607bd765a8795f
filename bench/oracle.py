"""The plain reference and the comparison that decides ``correct``.

``yen`` is textbook Yen over a Dijkstra with a binary heap: the k
shortest simple s-t paths of an undirected graph at one weight vector.
It imports nothing of the program.  ``rnd`` rounds every weight and
every sum; the identity gives the float64 reference, ``bf16`` the
control (the same search in the precision below the float32 the
configuration states).

``yen(..., rounds=1)`` is the approximate control: it takes every path
after the second from the deviations of the first alone, the shortcut
that skips Yen's later deviation rounds.

``judge`` compares one served answer with the reference.  Ties are
common (integer weights), so it compares what is well defined under
ties: the sorted multiset of the k smallest path lengths.  Each served
path must be a distinct simple s-t path of the graph at the epoch the
answer carries, and its length, recomputed here in float64, must agree
with the distance the answer states.
"""

from __future__ import annotations

import heapq
import struct

INF = float("inf")


def ident(x):
    return x


def bf16(x):
    """Round a float to the nearest bfloat16 (ties to even)."""
    if x == INF:
        return x
    b = struct.unpack("<I", struct.pack("<f", x))[0]
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("<f", struct.pack("<I", b))[0]


class Net:
    """Undirected adjacency at one weight vector: ``adj[u]`` lists
    ``(v, w)``, the lightest edge kept where two join the same pair."""

    def __init__(self, n, edge_u, edge_v, w, rnd=ident):
        best = {}
        for a, b, x in zip(edge_u.tolist(), edge_v.tolist(), w.tolist()):
            key = (a, b) if a < b else (b, a)
            if key not in best or x < best[key]:
                best[key] = x
        self.n = int(n)
        self.adj = [[] for _ in range(self.n)]
        self.w = {}
        for (a, b), x in best.items():
            x = rnd(x)
            self.adj[a].append((b, x))
            self.adj[b].append((a, x))
            self.w[(a, b)] = self.w[(b, a)] = x
        self.rnd = rnd

    def length(self, path):
        """Float64 length of a vertex path, or None if it is not one."""
        total = 0.0
        for a, b in zip(path, path[1:]):
            x = self.w.get((a, b))
            if x is None:
                return None
            total += x
        return total


def _dijkstra(net, src, dst, banned_v, banned_e):
    rnd = net.rnd
    dist = {src: 0.0}
    parent = {src: -1}
    heap = [(0.0, src)]
    done = set()
    while heap:
        d, u = heapq.heappop(heap)
        if u in done:
            continue
        if u == dst:
            path = [u]
            while parent[path[-1]] >= 0:
                path.append(parent[path[-1]])
            return d, path[::-1]
        done.add(u)
        for v, x in net.adj[u]:
            if v in banned_v or v in done or (u, v) in banned_e:
                continue
            nd = rnd(d + x)
            if nd < dist.get(v, INF):
                dist[v] = nd
                parent[v] = u
                heapq.heappush(heap, (nd, v))
    return INF, None


def yen(net, s, t, k, rounds=None):
    """[(dist, path)] of the k shortest simple s-t paths, ascending;
    with ``rounds``, deviations of only the first ``rounds`` paths."""
    if s == t:
        return [(0.0, (s,))]
    d0, p0 = _dijkstra(net, s, t, (), ())
    if p0 is None:
        return []
    found = [(d0, tuple(p0))]
    cand = []
    seen = {found[0][1]}
    rnd = net.rnd
    while len(found) < k:
        _, prev = found[-1]
        pre = 0.0
        deviate = rounds is None or len(found) <= rounds
        for i in range(len(prev) - 1 if deviate else 0):
            root = prev[:i + 1]
            banned_e = {(p[i], p[i + 1]) for _, p in found
                        if len(p) > i + 1 and p[:i + 1] == root}
            d, spur = _dijkstra(net, prev[i], t, set(root[:-1]), banned_e)
            if spur is not None:
                full = root[:-1] + tuple(spur)
                if full not in seen:
                    seen.add(full)
                    heapq.heappush(cand, (rnd(pre + d), full))
            pre = rnd(pre + net.w[(prev[i], prev[i + 1])])
        if not cand:
            break
        found.append(heapq.heappop(cand))
    return found


def judge(net, s, t, k, paths, ref):
    """(fault, gap) of one served answer against the reference.

    ``paths`` is the served [(dist, vertex tuple)], ``ref`` the
    reference's [(dist, path)] at the same epoch.  ``fault`` names the
    first structural fault (wrong count, a path that is not a simple
    s-t path of the graph, a repeated path) or is None; ``gap`` is the
    widest relative gap between a served distance and the reference's
    (sorted), or between a served distance and its path's length.
    """
    if len(paths) != len(ref):
        return f"{len(paths)} paths, reference has {len(ref)}", INF
    gap = 0.0
    served = set()
    for d, p in paths:
        p = tuple(int(v) for v in p)
        if not p or p[0] != s or p[-1] != t:
            return f"path {p[:3]}... does not join {s} and {t}", INF
        if len(set(p)) != len(p):
            return "path repeats a vertex", INF
        if p in served:
            return "path served twice", INF
        served.add(p)
        length = net.length(p)
        if length is None:
            return "path uses a missing edge", INF
        gap = max(gap, abs(length - float(d)) / max(length, 1e-300))
    got = sorted(float(d) for d, _ in paths)
    for a, (b, _) in zip(got, ref):
        gap = max(gap, abs(a - b) / max(b, 1e-300))
    return None, gap


def check_group(job):
    """Judge one epoch's answers: ``job`` is (n, edge_u, edge_v, w,
    [(s, t, k, served paths)]); returns [(fault, gap)] in order.  Runs
    in a worker process of the reference pool."""
    n, us, vs, w, items = job
    net = Net(n, us, vs, w)
    return [judge(net, s, t, k, paths, yen(net, s, t, k))
            for s, t, k, paths in items]
