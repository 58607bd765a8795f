"""Each answer is judged at the weights of the epoch it carries: the
harness's replay of the offered batches is what the program commits,
one batch at a time or coalesced, and the judge passes answers exact at
their epoch and fails the same queries answered at the first epoch's
weights but stamped later (the stale control)."""

import os
import sys
import types

import numpy as np
import pytest

import drive
import roadgen
import run
import traffic

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
FEED = {"interval_s": 0.25, "alpha": 0.1, "tau": 0.2, "feed_seed": 5}
LIMITS = {"bad_answers": 0, "dist_gap": 0.0}


@pytest.fixture(scope="module")
def program():
    sys.path.insert(0, SRC)
    from repro.core.graph import Graph, dedupe_updates
    from repro.core.sssp import graph_view
    from repro.core.yen import ksp
    return Graph, dedupe_updates, graph_view, ksp


@pytest.fixture(scope="module")
def net():
    n, us, vs, w0 = roadgen.grid_network(traffic.stream(4, "graph"), 10, 10,
                                         w_low=10, w_high=200)
    feed = traffic.Feed(2**31 + 7, "window", FEED, w0, 3.0)
    return n, us, vs, w0, [feed.batch(i) for i in range(len(feed))]


class Serial:
    """The reference pool's ``map``, in this process."""

    def map(self, f, jobs):
        return [f(j) for j in jobs]


def test_replay_is_what_the_program_commits(program, net):
    Graph, dedupe_updates, *_ = program
    n, us, vs, w0, batches = net
    weights = traffic.epoch_weights(w0, batches)
    one = Graph(n, us, vs, w0)
    for e, (eids, new_w) in enumerate(batches, start=1):
        one.apply_updates(eids, new_w)
        assert one.epoch == e and np.array_equal(one.w, weights[e])
    # the streaming handoff's coalescing: all batches, last write wins
    merged = Graph(n, us, vs, w0)
    merged.apply_updates(*dedupe_updates(
        np.concatenate([b[0] for b in batches]),
        np.concatenate([b[1] for b in batches])))
    assert np.array_equal(merged.w, weights[-1])
    assert not np.array_equal(weights[-1], w0)


def judge(net, answers):
    """``run.judge_run`` on a window whose queries carry ``answers``:
    (s, t, epoch stamped, served paths)."""
    n, us, vs, w0, batches = net
    win = drive.Window(3.0, 0.0)
    win.t_open, win.t_close = 0.0, 3.0
    win.updates = [drive.Update(e, w, 0.0) for e, w in batches]
    for s, t, epoch, paths in answers:
        q = drive.Query(s, t, 3, "window")
        q.submit, q.complete = 0.5, 1.0
        q.epoch_sub = q.epoch_done = epoch
        q.result = types.SimpleNamespace(paths=paths, epoch=epoch,
                                         truncated=False)
        win.queries.append(q)
    return run.judge_run(win, (n, us, vs), w0, LIMITS, Serial())


def answers_at(program, net, served_epoch):
    """24 queries stamped with epochs 1..12, each answered by the
    program's host Yen at the stamped epoch's weights, or at
    ``served_epoch``'s where that is given."""
    Graph, _, graph_view, ksp = program
    n, us, vs, w0, batches = net
    weights = traffic.epoch_weights(w0, batches)
    rng = np.random.default_rng(3)
    out = []
    for i in range(24):
        s, t = map(int, rng.choice(n, 2, replace=False))
        stamp = 1 + i % len(batches)
        at = stamp if served_epoch is None else served_epoch
        view = graph_view(Graph(n, us, vs, weights[at]))
        out.append((s, t, stamp, ksp(view, s, t, 3)))
    return out


def test_answers_exact_at_their_epoch_pass(program, net):
    correct, attempted, failed, ok, checks = judge(
        net, answers_at(program, net, None))
    assert correct and attempted == 24 and failed == 0 and len(ok) == 24
    assert checks["bad_answers"]["value"] == 0
    assert checks["dist_gap"]["value"] == 0.0


def test_stale_answers_fail(program, net):
    stale = answers_at(program, net, 0)
    correct, _, _, _, checks = judge(net, stale)
    assert not correct
    assert (checks["bad_answers"]["value"] > 0
            or checks["dist_gap"]["value"] > 0)
    # the same answers stamped with the epoch they were computed at pass
    at_zero = [(s, t, 0, paths) for s, t, _, paths in stale]
    assert judge(net, at_zero)[0]


def test_an_epoch_never_offered_is_bad(program, net):
    n, us, vs, w0, batches = net
    (s, t, _, paths), = answers_at(program, net, None)[:1]
    correct, _, _, _, checks = judge(net, [(s, t, len(batches) + 1, paths)])
    assert not correct and checks["bad_answers"]["value"] == 1


def test_feed_checks_count_a_batch_never_shown_at_the_drain_end():
    win = drive.Window(10.0, 5.0)
    win.t_end = 20.0
    shown, lost = drive.Update(None, None, 1.0), drive.Update(None, None, 2.0)
    shown.visible = 3.5
    win.updates = [shown, lost]
    checks = run.feed_checks(win, {"updates_lost": 0, "update_lag_s": 9.0})
    assert checks["updates_lost"] == {"value": 1, "limit": 0}
    assert checks["update_lag_s"] == {"value": 18.0, "limit": 9.0}
    lost.visible = 4.0
    checks = run.feed_checks(win, {"updates_lost": 0, "update_lag_s": 9.0})
    assert checks["updates_lost"]["value"] == 0
    assert checks["update_lag_s"]["value"] == 2.5


@pytest.mark.parametrize("kind", ["stale", "bf16", "approx"])
def test_controls_fail_on_a_feed(kind):
    import control

    cfg = {"graph": {"seed": 0, "rows": 10, "cols": 10, "knockout": 0.08,
                     "shortcut_frac": 0.03, "w_low": 10, "w_high": 200,
                     "directed": False}}
    mix = {"clients": 16, "k": 3, "pool_seed": 3, "order_block": 16,
           "max_queries": 200, "warmup_seconds": 1, "updates": FEED}
    r = control.readings(kind, 2**31 + 3, cfg, mix, 60, 3.0, Serial())
    assert r["answers"] == 60 and r["epochs"] == 13  # 4 to 4 + 12
    assert r["bad_answers"] > 0 or r["dist_gap"] > 0
    if kind == "stale":
        # without a feed every query is at the first epoch: the stale
        # control is the reference itself
        plain = {k: v for k, v in mix.items() if k != "updates"}
        r = control.readings(kind, 2**31 + 3, cfg, plain, 60, 3.0, Serial())
        assert r["bad_answers"] == 0 and r["dist_gap"] == 0.0
