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


def reference_k_best_joins(segments, k):
    """The join as it was before per-cohort prep, the incremental bound
    and the cutoff: every segment list prepared per reference, and each
    push's bound rescanned over every remaining segment."""
    import heapq

    m = len(segments)
    if any(not seg for seg in segments):
        return []
    joints = [seg[0][1][0] for seg in segments] + [segments[-1][0][1][-1]]
    if len(set(joints)) != len(joints):
        return []
    joint_set = set(joints)
    opts = []
    for seg in segments:
        keep = []
        for d, p in sorted(seg, key=lambda e: e[0]):
            inner = set(p[1:-1])
            if len(inner) == len(p) - 2 and joint_set.isdisjoint(inner):
                keep.append((d, frozenset(inner), p))
        if not keep:
            return []
        opts.append(keep)

    def bound(j, used):
        total = 0.0
        for seg in opts[j:]:
            best = next((d for d, inner, _ in seg if used.isdisjoint(inner)),
                        None)
            if best is None:
                return None
            total += best
        return total

    heap = [(bound(0, frozenset()), (), 0.0, frozenset())]
    out = []
    while heap and len(out) < k:
        _, idx, g, used = heapq.heappop(heap)
        j = len(idx)
        if j == m:
            verts = list(opts[0][idx[0]][2])
            for jj in range(1, m):
                verts.extend(opts[jj][idx[jj]][2][1:])
            out.append((g, tuple(verts)))
            continue
        for i, (d, inner, _) in enumerate(opts[j]):
            if used.isdisjoint(inner):
                nxt = used | inner
                h = bound(j + 1, nxt)
                if h is not None:
                    heapq.heappush(heap, (g + d + h, idx + (i,), g + d, nxt))
    return out


def k_best_joins(segments, k, cutoff=None, stats=None):
    """The stepper's join of one reference over its own segment lists."""
    from repro.core.kspdg import QueryStats, _JoinPrep

    return _JoinPrep(segments).k_best_joins(
        range(len(segments)), k, cutoff,
        QueryStats() if stats is None else stats)


class ReferenceJoin:
    """Stands in for the stepper's per-cohort join with the reference
    above: no shared prep, no incremental bound, no cutoff."""

    def __init__(self, seg_lists):
        self.seg_lists = seg_lists

    def k_best_joins(self, idxs, k, cutoff, stats):
        stats.joins += 1
        return reference_k_best_joins([self.seg_lists[j] for j in idxs], k)


def random_segment(rng, a, b, width, n_vertices, lengths=(1, 20)):
    """≤ width distinct a→b entries with random detours, ascending."""
    entries = set()
    while len(entries) < width:
        mid = rng.choice(n_vertices, rng.integers(0, 3), replace=False)
        p = (int(a), *(int(v) for v in mid if v not in (a, b)), int(b))
        entries.add((float(rng.integers(*lengths)), p))
    return sorted(entries)


class TestKBestJoins:
    """The splice of per-pair partial KSPs into whole candidates."""

    @staticmethod
    def brute_force(segments, k=None):
        """Every simple join, ascending by (length, path); the first k."""
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
    def random_segments(rng, m, width, n_vertices, lengths=(1, 20)):
        """m chained segments of ≤ width entries each, ascending, with
        random detours that often collide with other segments."""
        joints = rng.choice(n_vertices, m + 1, replace=False)
        return [random_segment(rng, a, b, width, n_vertices, lengths)
                for a, b in zip(joints, joints[1:])]

    def joinable(self, rng, least, **kw):
        """Random segments with at least ``least`` simple joins."""
        while True:
            segs = self.random_segments(rng, int(rng.integers(2, 6)), 3, 14,
                                        **kw)
            if len(self.brute_force(segs)) >= least:
                return segs

    def check(self, got, segs, k, cutoff=None):
        """``got`` holds the k best simple joins not over ``cutoff``:
        their lengths are the brute force's, each is a simple join of
        ``segs``, and they come ascending by (length, path)."""
        every = self.brute_force(segs)
        want = [x for x in every[:k] if cutoff is None or x[0] <= cutoff]
        assert [d for d, _ in got] == [d for d, _ in want]
        assert got == sorted(got)
        assert set(got) <= set(every)

    CASES = ([pytest.param("plain", s, id=str(s)) for s in range(6)]
             + [pytest.param(kind, s, id=f"{kind}-{s}")
                for kind in ("cut-below", "cut-at-first", "cut-at-kth",
                             "shared", "ties")
                for s in range(3)]
             + [pytest.param("collide", 0, id="collide")])

    @pytest.mark.parametrize("kind, seed", CASES)
    def test_matches_brute_force(self, kind, seed):
        from repro.core.kspdg import QueryStats, _JoinPrep

        rng = np.random.default_rng(seed)
        if kind == "plain":
            segs = self.random_segments(rng, int(rng.integers(1, 6)), 3, 14)
            self.check(k_best_joins(segs, 3), segs, 3)
        elif kind.startswith("cut-"):
            # the cutoff: below every join, or exactly at the first or
            # the k-th join's length, where a join tied with it is kept
            segs = self.joinable(rng, 2)
            want = self.brute_force(segs, 3)
            first, kth = want[0][0], want[-1][0]
            cutoff = {"cut-below": first - 0.5, "cut-at-first": first,
                      "cut-at-kth": kth}[kind]
            st = QueryStats()
            got = k_best_joins(segs, 3, cutoff, st)
            self.check(got, segs, 3, cutoff)
            assert st.joins == 1
            if kind == "cut-below":
                assert got == [] and st.joins_cut == 1 and st.join_pops == 0
            else:
                assert got[-1][0] == cutoff and st.joins_cut == 0
        elif kind == "shared":
            # two references share their first pair, whose entries run
            # through a vertex that is a joint of the second reference
            # only: one preparation, filtered per reference
            m = int(rng.integers(2, 5))
            joints = [int(v) for v in rng.choice(14, m + 1, replace=False)]
            seg_lists = [random_segment(rng, a, b, 3, 14)
                         for a, b in zip(joints, joints[1:])]
            inner = {v for _, p in seg_lists[0] for v in p[1:-1]}
            x = min(inner - set(joints), default=None)
            if x is None:  # no detour on the first pair: give it one
                x = min(set(range(14)) - set(joints))
                seg_lists[0] = sorted(seg_lists[0]
                                      + [(1.0, (joints[0], x, joints[1]))])
            a1, last = joints[1], joints[-1]
            seg_lists += [random_segment(rng, a1, x, 3, 14),
                          random_segment(rng, x, last, 3, 14)]
            refs = [list(range(m)), [0, m, m + 1]]
            prep = _JoinPrep(seg_lists)
            for k in (3, 50):
                for idxs in refs:
                    got = prep.k_best_joins(idxs, k, None, QueryStats())
                    self.check(got, [seg_lists[j] for j in idxs], k)
            assert len(prep.prepped) == m + 2  # each pair prepared once
        elif kind == "collide":
            # the first entry moves segment 2's best past vertex 10; the
            # second segment's shortest entry then collides with that
            # new best (11) and not with any segment's first entry
            segs = [[(1.0, (0, 10, 1))],
                    [(1.0, (1, 11, 2)), (5.0, (1, 12, 2))],
                    [(1.0, (2, 10, 3)), (2.0, (2, 11, 3)), (3.0, (2, 13, 3))]]
            got = k_best_joins(segs, 3)
            self.check(got, segs, 3)
            assert [d for d, _ in got] == [5.0, 8.0, 9.0]
        else:
            # equal lengths, different paths: with k past every join the
            # whole list is the brute force's, path order included
            segs = self.joinable(rng, 4, lengths=(1, 3))
            every = self.brute_force(segs)
            assert len({d for d, _ in every}) < len(every)  # ties exist
            assert k_best_joins(segs, len(every) + 1) == every
            self.check(k_best_joins(segs, 3), segs, 3)

    def test_no_simple_join_returns_without_enumerating(self):
        """Every entry of the first segment runs through every detour of
        the second, so no join is simple: the answer is [] after the
        two-segment prefixes, not after 3**30 index tuples."""
        m = 30
        detours = (2000 * m, 2000 * m + 1, 2000 * m + 2)
        segs = [[(1.0, (0, *detours, 100 + i, m)) for i in range(3)]]
        segs += [[(float(i + 1), (j, 2000 * j + i, j + 1)) for i in range(3)]
                 for j in range(m, 2 * m - 1)]
        assert k_best_joins(segs, 3) == []


class TestJoinStepper:
    """The stepper's joins give the answers the reference join gave,
    under every reference stream and variant that runs them."""

    @pytest.mark.parametrize("stream, variant", [
        ("lazy", None), ("yen", None), ("lazy", "diverse"),
        ("lazy", "bounded")])
    def test_answers_match_reference_join(self, setup, monkeypatch, stream,
                                          variant):
        from repro.core import kspdg
        from repro.core.variants import make_variant

        g, d, queries = setup
        policy = make_variant(variant)
        k = 3

        def run(s, t):
            return ksp_dg(d, s, t, k, ref_stream=stream, variant=policy,
                          return_stats=True)

        got = [run(s, t) for s, t in queries]
        with monkeypatch.context() as mp:
            mp.setattr(kspdg, "_JoinPrep", ReferenceJoin)
            want = [run(s, t) for s, t in queries]
        view = graph_view(g)
        for (s, t), (L, st), (L_ref, st_ref) in zip(queries, got, want):
            assert L == L_ref, (s, t)
            assert (st.references, st.iterations, st.joins) == (
                st_ref.references, st_ref.iterations, st_ref.joins)
            assert st.join_pops > 0
            if variant is None:
                yen = ksp(view, s, t, k)
                assert [round(x, 8) for x, _ in L] == [
                    round(x, 8) for x, _ in yen], (s, t)

    def test_cutoff_ends_joins_at_the_root(self, setup):
        """Once L holds k paths, a reference whose every join is longer
        than L's k-th is cut before its search pops anything."""
        _, d, queries = setup
        stats = [ksp_dg(d, s, t, 3, ref_stream="lazy", return_stats=True)[1]
                 for s, t in queries]
        assert sum(st.joins_cut for st in stats) > 0
        assert all(st.joins_cut <= st.joins for st in stats)
