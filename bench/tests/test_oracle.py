"""The reference and the comparison: the copies agree with the program's
own generator and host Yen, and the judge catches each kind of fault."""

import os
import sys

import numpy as np
import pytest

import oracle
import roadgen
import traffic

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")


@pytest.fixture(scope="module")
def program():
    sys.path.insert(0, SRC)
    from repro.core.graph import Graph
    from repro.core.sssp import graph_view
    from repro.core.yen import ksp
    from repro.data.roadnet import grid_road_network
    return Graph, graph_view, ksp, grid_road_network


def small(seed=3, rows=12):
    return roadgen.grid_network(np.random.default_rng(seed), rows, rows)


def test_grid_copy_draws_what_the_program_draws(program):
    *_, grid_road_network = program
    n, us, vs, w0 = small(seed=9)
    g = grid_road_network(12, 12, seed=9)
    assert n == g.n
    assert np.array_equal(us, g.edge_u) and np.array_equal(vs, g.edge_v)
    assert np.array_equal(w0, g.w0)


@pytest.mark.parametrize("k", [1, 3, 6])
def test_reference_matches_program_host_yen(program, k):
    Graph, graph_view, ksp, _ = program
    n, us, vs, w0 = small()
    w = w0 * np.random.default_rng(1).uniform(0.8, 1.2, w0.shape)
    view = graph_view(Graph(n, us, vs, w))
    net = oracle.Net(n, us, vs, w)
    rng = np.random.default_rng(2)
    for _ in range(15):
        s, t = map(int, rng.choice(n, 2, replace=False))
        ref = oracle.yen(net, s, t, k)
        want = ksp(view, s, t, k)
        assert [round(d, 9) for d, _ in ref] == [round(d, 9) for d, _ in want]
        fault, gap = oracle.judge(net, s, t, k, want, ref)
        assert fault is None and gap < 1e-12  # summation order alone


def test_judge_names_each_fault():
    n, us, vs, w0 = small()
    net = oracle.Net(n, us, vs, w0)
    s, t = 0, n - 1
    ref = oracle.yen(net, s, t, 3)
    (d0, p0), (d1, p1), _ = ref
    assert oracle.judge(net, s, t, 3, ref[:2], ref)[0].startswith("2 paths")
    assert "twice" in oracle.judge(net, s, t, 3, [ref[0]] * 3, ref)[0]
    loop = (d0, p0[:2] + p0[:2] + p0[2:])
    assert "repeats" in oracle.judge(net, s, t, 3, [loop] + ref[1:], ref)[0]
    wrong_end = (d0, p0[:-1])
    assert "join" in oracle.judge(net, s, t, 3, [wrong_end] + ref[1:],
                                  ref)[0]
    fault, gap = oracle.judge(net, s, t, 3, [(d0 + 1, p0)] + ref[1:], ref)
    assert fault is None and gap == pytest.approx(1 / d0)


def test_bf16_rounds_to_nearest_even():
    assert oracle.bf16(257.0) == 256.0
    assert oracle.bf16(259.0) == 260.0
    assert oracle.bf16(1.0) == 1.0
    assert oracle.bf16(oracle.INF) == oracle.INF


def test_controls_fail_the_comparison():
    """Each control, at a small size and the cell's integer travel times,
    gives a gap over the exact comparison's limit of 0."""
    n, us, vs, w0 = roadgen.grid_network(traffic.stream(4, "graph"), 14, 14,
                                         w_low=10, w_high=200)
    ref = oracle.Net(n, us, vs, w0)
    low = oracle.Net(n, us, vs, w0, oracle.bf16)
    gaps = {1: 0.0, None: 0.0}
    rng = np.random.default_rng(6)
    for _ in range(40):
        s, t = map(int, rng.choice(n, 2, replace=False))
        want = oracle.yen(ref, s, t, 3)
        for served, rounds in ((ref, 1), (low, None)):
            fault, gap = oracle.judge(ref, s, t, 3,
                                      oracle.yen(served, s, t, 3, rounds),
                                      want)
            gaps[rounds] = max(gaps[rounds], gap)
        fault, gap = oracle.judge(ref, s, t, 3, want, want)
        assert fault is None and gap == 0.0  # the reference is exact
    assert min(gaps.values()) > 1e-4
