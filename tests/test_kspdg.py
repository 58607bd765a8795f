"""KSP-DG end-to-end exactness (Section 5, Theorem 3) on dynamic graphs."""

import numpy as np
import pytest

from repro.core.dtlp import DTLP
from repro.core.kspdg import PartialKSPCache, ksp_dg
from repro.core.sssp import graph_view
from repro.core.yen import ksp
from repro.data.roadnet import WeightUpdateStream, grid_road_network


def check_queries(dtlp, g, queries, k, **kw):
    view = graph_view(g)
    for s, t in queries:
        got = ksp_dg(dtlp, s, t, k, **kw)
        want = ksp(view, s, t, k)
        assert [round(d, 8) for d, _ in got] == [
            round(d, 8) for d, _ in want
        ], (s, t)
        for d, p in got:
            assert p[0] == s and p[-1] == t and len(set(p)) == len(p)
            assert abs(g.path_distance(p) - d) < 1e-8


@pytest.fixture(scope="module")
def setup():
    g = grid_road_network(12, 12, seed=0)
    d = DTLP.build(g, z=20, xi=4)
    rng = np.random.default_rng(42)
    queries = [
        tuple(map(int, rng.choice(g.n, size=2, replace=False)))
        for _ in range(12)
    ]
    return g, d, queries


@pytest.mark.parametrize("k", [1, 2, 5])
def test_exactness(setup, k):
    g, d, queries = setup
    check_queries(d, g, queries, k)


@pytest.mark.parametrize("mode", ["yen", "para_yen", "pyen"])
def test_partial_modes_match(setup, mode):
    """KSP-DG, KSP-DG-Yen, Para-KSP-DG must all be exact (Section 6.5)."""
    g, d, queries = setup
    check_queries(d, g, queries[:6], 3, partial_mode=mode)


def test_exactness_under_updates():
    g = grid_road_network(10, 10, seed=3)
    d = DTLP.build(g, z=16, xi=4)
    stream = WeightUpdateStream(g, alpha=0.5, tau=0.5, seed=7)
    rng = np.random.default_rng(0)
    for round_ in range(3):
        eids, new_w = stream.next_batch()
        d.apply_updates(eids, new_w)
        qs = [
            tuple(map(int, rng.choice(g.n, size=2, replace=False)))
            for _ in range(6)
        ]
        check_queries(d, g, qs, 3)


def test_boundary_endpoints(setup):
    g, d, queries = setup
    boundary = np.nonzero(d.partition.is_boundary)[0]
    rng = np.random.default_rng(5)
    qs = [
        tuple(map(int, rng.choice(boundary, size=2, replace=False)))
        for _ in range(6)
    ]
    check_queries(d, g, qs, 3)


def test_same_vertex_query(setup):
    g, d, _ = setup
    assert ksp_dg(d, 4, 4, 3) == [(0.0, (4,))]


class TestPartialKSPCacheLRU:
    def test_eviction_order(self):
        c = PartialKSPCache(max_entries=3)
        c.put("a", 1)
        c.put("b", 2)
        c.put("c", 3)
        assert c.get("a") == 1  # refresh "a": "b" is now the LRU entry
        c.put("d", 4)
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3 and c.get("d") == 4
        assert len(c) == 3

    def test_put_refreshes_existing_key(self):
        c = PartialKSPCache(max_entries=2)
        c.put("a", 1)
        c.put("b", 2)
        c.put("a", 10)  # overwrite refreshes recency, must not evict
        c.put("c", 3)
        assert c.get("b") is None  # "b" was least recently used
        assert c.get("a") == 10 and c.get("c") == 3

    def test_version_bump_invalidation(self):
        """ksp_dg keys include the graph version: a weight update makes
        old entries unreachable, and a bounded cache ages them out
        instead of flushing the live working set."""
        g = grid_road_network(8, 8, seed=11)
        d = DTLP.build(g, z=12, xi=4)
        cache = PartialKSPCache(max_entries=64)
        check_queries(d, g, [(0, g.n - 1)], 3, cache=cache)
        v0_keys = [key for key in cache.data if key[0] == g.version]
        assert v0_keys
        stream = WeightUpdateStream(g, alpha=0.5, tau=0.5, seed=12)
        eids, new_w = stream.next_batch()
        d.apply_updates(eids, new_w)
        # post-bump queries are exact and never read stale-version entries
        check_queries(d, g, [(0, g.n - 1)], 3, cache=cache)
        assert any(key[0] == g.version for key in cache.data)
        assert len(cache) <= 64


def test_partial_cache_reuse(setup):
    g, d, queries = setup
    cache = PartialKSPCache()
    check_queries(d, g, queries[:6], 3, cache=cache)
    check_queries(d, g, queries[:6], 3, cache=cache)  # warm pass still exact


def test_interior_endpoints_same_subgraph(setup):
    """Both endpoints non-boundary inside the SAME subgraph: the spliced
    skeleton must still see paths that leave and re-enter the subgraph
    (the cluster routes these pairs to the single home worker)."""
    g, d, _ = setup
    ib = d.partition.is_boundary
    checked = 0
    for sg in d.partition.subgraphs:
        interior = [int(v) for v in sg.vertices if not ib[v]]
        if len(interior) >= 2:
            check_queries(d, g, [(interior[0], interior[-1])], 4)
            checked += 1
        if checked == 3:
            break
    assert checked, "partition has no subgraph with two interior vertices"


def test_k_exceeds_simple_path_count():
    """k larger than the number of existing simple paths: ksp_dg must
    return them all and terminate (no padding, no spin)."""
    from repro.core.graph import Graph

    # path graph 0-1-2-3-4: exactly ONE simple path end to end
    u = np.array([0, 1, 2, 3])
    v = np.array([1, 2, 3, 4])
    w = np.array([1.0, 2.0, 3.0, 4.0])
    g = Graph(5, u, v, w)
    d = DTLP.build(g, z=2, xi=3)
    assert ksp_dg(d, 0, 4, 5) == [(10.0, (0, 1, 2, 3, 4))]

    # diamond with a pendant: exactly two simple 0→3 paths
    u2 = np.array([0, 1, 0, 2, 2])
    v2 = np.array([1, 2, 2, 3, 4])
    w2 = np.array([1.0, 1.0, 2.5, 1.0, 1.0])
    g2 = Graph(5, u2, v2, w2)
    d2 = DTLP.build(g2, z=3, xi=3)
    got = ksp_dg(d2, 0, 3, 10)
    view = graph_view(g2)
    assert got == ksp(view, 0, 3, 10)
    assert len(got) == 2


def test_termination_stats(setup):
    """Theorem 3's stopping rule: iterations are finite and small for k=2."""
    g, d, queries = setup
    for s, t in queries[:6]:
        res, stats = ksp_dg(d, s, t, 2, return_stats=True)
        assert stats.iterations < 60


def test_directed_splice_uses_reverse_distances():
    """Regression: a spliced (non-boundary) destination on a DIRECTED
    graph needs boundary→t splice edges from a reverse-edge Dijkstra.
    The old forward-only splice gave t→boundary distances, so on an
    asymmetric graph the extended skeleton had no (or wrongly weighted)
    way INTO t — e.g. on a pure directed cycle every query ending at an
    interior vertex returned no paths at all."""
    from repro.core.graph import Graph

    # directed 6-cycle 0→1→…→5→0, asymmetric by construction
    u = np.arange(6)
    v = (u + 1) % 6
    w = np.arange(1.0, 7.0)
    g = Graph(6, u, v, w, directed=True)
    d = DTLP.build(g, z=3, xi=4)
    assert not d.partition.is_boundary[1]  # t interior: the broken case
    view = graph_view(g)
    for s in range(6):
        for t in range(6):
            if s == t:
                continue
            got = ksp_dg(d, s, t, 3)
            want = ksp(view, s, t, 3, directed=True)
            assert [round(x, 8) for x, _ in got] == [
                round(x, 8) for x, _ in want
            ], (s, t)


def test_directed_graph_kspdg():
    from repro.core.graph import Graph

    rng = np.random.default_rng(9)
    # random strongly-connected-ish directed graph: ring + chords
    n = 40
    u = list(range(n))
    v = [(i + 1) % n for i in range(n)]
    for _ in range(80):
        a, b = rng.integers(0, n, size=2)
        if a != b:
            u.append(int(a))
            v.append(int(b))
    w = rng.uniform(1.0, 10.0, size=len(u))
    g = Graph(n, np.array(u), np.array(v), w, directed=True)
    d = DTLP.build(g, z=10, xi=4)
    view = graph_view(g)
    for _ in range(8):
        s, t = map(int, rng.choice(n, size=2, replace=False))
        got = ksp_dg(d, s, t, 3)
        want = ksp(view, s, t, 3, directed=True)
        assert [round(x, 8) for x, _ in got] == [round(x, 8) for x, _ in want]


class TestKBestJoins:
    """The splice of per-pair partial KSPs into whole candidates."""

    @staticmethod
    def brute_force(segments, k):
        import itertools

        out = []
        for combo in itertools.product(*segments):
            verts = list(combo[0][1])
            for _, p in combo[1:]:
                verts.extend(p[1:])
            if len(set(verts)) == len(verts):
                out.append((sum(d for d, _ in combo), tuple(verts)))
        return sorted(out)[:k]

    @staticmethod
    def random_segments(rng, m, width, n_vertices):
        """m chained segments of ≤ width entries each, ascending, with
        random detours that often collide with other segments."""
        joints = rng.choice(n_vertices, m + 1, replace=False)
        segments = []
        for a, b in zip(joints, joints[1:]):
            entries = set()
            while len(entries) < width:
                mid = rng.choice(n_vertices, rng.integers(0, 3),
                                 replace=False)
                p = (int(a), *(int(v) for v in mid if v not in (a, b)),
                     int(b))
                entries.add((float(rng.integers(1, 20)), p))
            segments.append(sorted(entries))
        return segments

    @pytest.mark.parametrize("seed", range(6))
    def test_matches_brute_force(self, seed):
        from repro.core.kspdg import _k_best_joins

        rng = np.random.default_rng(seed)
        segs = self.random_segments(rng, int(rng.integers(1, 6)), 3, 14)
        got = _k_best_joins(segs, 3)
        want = self.brute_force(segs, 3)
        assert [d for d, _ in got] == [d for d, _ in want]
        assert all(len(set(p)) == len(p) for _, p in got)

    def test_no_simple_join_returns_without_enumerating(self):
        """Every entry of the first segment runs through every detour of
        the second, so no join is simple: the answer is [] after the
        two-segment prefixes, not after 3**30 index tuples."""
        from repro.core.kspdg import _k_best_joins

        m = 30
        detours = (2000 * m, 2000 * m + 1, 2000 * m + 2)
        segs = [[(1.0, (0, *detours, 100 + i, m)) for i in range(3)]]
        segs += [[(float(i + 1), (j, 2000 * j + i, j + 1)) for i in range(3)]
                 for j in range(m, 2 * m - 1)]
        assert _k_best_joins(segs, 3) == []
