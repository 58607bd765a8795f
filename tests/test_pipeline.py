"""Async pipelined scheduler: byte-identical determinism vs the lockstep
reference schedule (including across an UpdateBatch epoch barrier with a
mid-batch worker kill/revive), per-worker pipeline dedup accounting,
occupancy and reference-stream stats, and the sharpened
next-simple-reference stop rule on a continuous-weight grid."""

import numpy as np
import pytest

from repro.core.dtlp import DTLP
from repro.core.kspdg import ksp_dg
from repro.data.roadnet import WeightUpdateStream, grid_road_network
from repro.dist.cluster import Cluster
from repro.dist.scheduler import QueryScheduler
from repro.service import KSPService, QueryRequest, ServiceConfig, UpdateBatch


@pytest.fixture(scope="module")
def net():
    g = grid_road_network(10, 10, seed=2)
    return g, DTLP.build(g, z=16, xi=4)


def rand_queries(g, n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        tuple(map(int, rng.choice(g.n, size=2, replace=False)))
        for _ in range(n)
    ]


def mixed_ks(n, seed=0):
    """Power-law-ish mixed k per query: mostly small, a heavy tail."""
    rng = np.random.default_rng(seed)
    return [int(np.clip(rng.zipf(2.0), 1, 6)) for _ in range(n)]


class TestOutOfOrderDeterminism:
    @pytest.mark.parametrize("engine", ["pyen", "dense_bf"])
    def test_mixed_trace_matches_lockstep(self, net, engine):
        """The same seeded mixed-size trace through the lockstep
        (pipeline=False) and async schedulers must produce byte-identical
        paths, epochs, and per-query reference counts — pipelining
        reorders dispatch and completion, never the math."""
        g, d = net
        qs = rand_queries(g, 10, seed=31)
        ks = mixed_ks(10, seed=32)

        def serve(pipeline):
            sched = QueryScheduler(
                Cluster(d, n_workers=4, engine=engine),
                max_in_flight=5, pipeline=pipeline, pipeline_depth=2,
            )
            tickets = [sched.submit(s, t, k) for (s, t), k in zip(qs, ks)]
            sched.drain()
            return sched, tickets

        lock_sched, lock = serve(False)
        pipe_sched, pipe = serve(True)
        for ltk, ptk in zip(lock, pipe):
            assert ptk.result == ltk.result
            assert ptk.epoch == ltk.epoch
            assert ptk.stats.references == ltk.stats.references
            assert ptk.stats.iterations == ltk.stats.iterations
            assert ptk.ticks == ltk.ticks
        # gather sees the same per-round tasks in both schedules
        assert (pipe_sched.stats.tasks_requested
                == lock_sched.stats.tasks_requested)
        assert pipe_sched.stats.tasks_deduped >= 0

    def test_update_barrier_with_mid_batch_kill_revive(self):
        """Determinism holds across an UpdateBatch epoch barrier with a
        worker killed mid-batch (its queued batches re-route to the
        replica) and revived after (it re-syncs before serving).

        Deliberately NOT on the shared ``net`` fixture: applying the
        UpdateBatch patches the graph/DTLP in place, so each mode must
        serve its own pristine build or the second run starts at the
        first run's post-update epoch and weights."""

        def build():
            g = grid_road_network(10, 10, seed=2)
            return g, DTLP.build(g, z=16, xi=4)

        g0, _ = build()
        stream = WeightUpdateStream(g0, alpha=0.5, tau=0.5, seed=41)
        eids, new_w = stream.next_batch()
        qs1 = rand_queries(g0, 6, seed=43)
        qs2 = rand_queries(g0, 6, seed=44)
        ks1 = mixed_ks(6, seed=45)
        ks2 = mixed_ks(6, seed=46)

        def serve(pipeline):
            _, d = build()
            # max_in_flight covers the whole first wave so the epoch
            # split is trace-determined: admission timing (lockstep
            # admits at tick boundaries, pipelined admits mid-pump as
            # slots free) must not decide who crosses the barrier
            cfg = ServiceConfig(engine="pyen", n_workers=4, max_in_flight=8,
                                pipeline=pipeline)
            svc = KSPService(d, cfg)
            tickets = [svc.submit(QueryRequest(s, t, k))
                       for (s, t), k in zip(qs1, ks1)]
            # partially advance the first wave, then kill a worker with
            # queries (and, pipelined, dispatched batches) in flight
            for _ in range(3):
                svc.tick()
            svc.kill(1)
            # epoch barrier while the first wave still drains
            svc.update(UpdateBatch(eids, new_w))
            tickets += [svc.submit(QueryRequest(s, t, k))
                        for (s, t), k in zip(qs2, ks2)]
            svc.drain()
            svc.revive(1)
            post = svc.query(*qs1[0], k=3)
            return tickets, post

        lock, lock_post = serve(False)
        pipe, pipe_post = serve(True)
        for ltk, ptk in zip(lock, pipe):
            assert ptk.result.paths == ltk.result.paths
            assert ptk.result.epoch == ltk.result.epoch
            assert (ptk.result.stats.references
                    == ltk.result.stats.references)
        # first wave answered pre-update, second wave post-update
        assert {tk.result.epoch for tk in lock[:6]} == {0}
        assert {tk.result.epoch for tk in lock[6:]} == {1}
        assert pipe_post.paths == lock_post.paths
        assert pipe_post.epoch == lock_post.epoch == 1


class TestPipelineStats:
    @pytest.mark.parametrize("pipeline", [False, True])
    def test_reference_counters_sum_finished_queries(self, net, pipeline):
        """``references``/``walks_skipped`` are the sums of the finished
        queries' own QueryStats, in both schedules, and reach the
        service snapshot's scheduler section."""
        g, d = net
        svc = KSPService(d, ServiceConfig(engine="pyen", n_workers=2,
                                          pipeline=pipeline))
        res = [svc.query(s, t, k=3) for s, t in rand_queries(g, 6, seed=57)]
        sched = svc.snapshot()["scheduler"]
        assert sched["completed"] == len(res)
        assert sched["references"] == sum(r.stats.references for r in res)
        assert sched["walks_skipped"] == sum(r.stats.walks_skipped
                                             for r in res)
        assert sched["walks_skipped"] > 0  # the lazy stream's walks
        assert "idle_fracs" not in sched

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_join_counters_sum_finished_queries(self, net, pipeline):
        """``joins``/``joins_cut``/``join_pops`` are the sums of the
        finished queries' own QueryStats, in both schedules, and reach
        the service snapshot's scheduler section."""
        g, d = net
        svc = KSPService(d, ServiceConfig(engine="pyen", n_workers=2,
                                          pipeline=pipeline))
        res = [svc.query(s, t, k=3) for s, t in rand_queries(g, 6, seed=57)]
        sched = svc.snapshot()["scheduler"]
        for key in ("joins", "joins_cut", "join_pops"):
            assert sched[key] == sum(getattr(r.stats, key) for r in res), key
        assert 0 <= sched["joins_cut"] <= sched["joins"]
        assert sched["join_pops"] > 0

    def test_idle_and_occupancy_stats(self, net):
        """The pipeline exports what the bench gate needs: per-worker
        busy time against working wall time, peak in-flight batches,
        reference-stream counts summed over the finished queries, and
        dedup accounting that stays an invariant of requested/dispatched."""
        g, d = net
        qs = rand_queries(g, 8, seed=51) * 2  # guaranteed overlap
        sched = QueryScheduler(Cluster(d, n_workers=4, engine="dense_bf"),
                               max_in_flight=8)
        tickets = sched.run(qs, 3)
        st = sched.stats
        assert st.working_s > 0.0
        assert st.worker_busy_s and all(v >= 0.0
                                        for v in st.worker_busy_s.values())
        assert st.references == sum(tk.stats.references for tk in tickets)
        assert st.references >= len(tickets)
        assert st.walks_skipped == sum(tk.stats.walks_skipped
                                       for tk in tickets)
        assert st.max_inflight_batches >= 1
        assert st.batches_dispatched >= 1
        assert st.tasks_dispatched < st.tasks_requested
        assert st.tasks_deduped == st.tasks_requested - st.tasks_dispatched

    def test_twins_collapse_in_pipeline(self, net):
        """Identical concurrent queries share every batch through the
        per-worker join index, exactly like the lockstep tick merge."""
        g, d = net
        s, t = rand_queries(g, 1, seed=53)[0]
        for pipeline in (False, True):
            bat = Cluster(d, n_workers=4, engine="pyen")
            sched = QueryScheduler(bat, max_in_flight=2, pipeline=pipeline)
            tickets = sched.run([(s, t), (s, t)], 3)
            assert tickets[0].result == tickets[1].result
            assert sched.stats.tasks_deduped > 0
            # twins fully collapse: exactly half the tasks dispatch
            assert (sched.stats.tasks_dispatched * 2
                    == sched.stats.tasks_requested)

    def test_immediate_completion_stamps(self, net):
        """Pipelined completions are stamped mid-pump: every ticket's
        clocks stay ordered and finite under mixed-size load."""
        g, d = net
        qs = rand_queries(g, 6, seed=55)
        ks = mixed_ks(6, seed=56)
        sched = QueryScheduler(Cluster(d, n_workers=4, engine="pyen"),
                               max_in_flight=6)
        tickets = [sched.submit(s, t, k) for (s, t), k in zip(qs, ks)]
        sched.drain()
        for tk in tickets:
            assert tk.done
            assert tk.admitted_at >= tk.arrival
            assert tk.finished_at >= tk.admitted_at
            assert tk.finished_at <= sched.clock + 1e-9

    def test_predicted_wait_tracks_pipe_depth(self, net):
        """The admission signal reflects per-worker backlog once solve
        EWMAs exist, and stays zero on a cold scheduler."""
        g, d = net
        sched = QueryScheduler(Cluster(d, n_workers=2, engine="pyen"),
                               max_in_flight=4)
        assert sched.predicted_wait() == 0.0
        sched.run(rand_queries(g, 4, seed=57), 3)
        # drained: no backlog, so only the (empty) queue term remains
        assert sched.predicted_wait() == 0.0
        pipes = [p for p in sched._pipes.values() if p.solve_samples]
        assert pipes and all(p.solve_ewma > 0.0 for p in pipes)


class TestSharpenedStopRule:
    def test_exact_and_cohort_count_on_continuous_grid(self, net):
        """Regression for the next-simple-reference stop rule: on a
        continuous-weight grid the lazy stream consumes non-simple walks
        through the bound scan (walks_skipped), stops within the pinned
        cohort budget, and stays exact vs the all-simple yen stream."""
        g, d = net
        rng = np.random.default_rng(3)
        cohorts = 0
        skipped = 0
        for _ in range(8):
            s, t = map(int, rng.choice(g.n, size=2, replace=False))
            L, st = ksp_dg(d, s, t, 4, ref_stream="lazy", return_stats=True)
            L_yen, _ = ksp_dg(d, s, t, 4, ref_stream="yen",
                              return_stats=True)
            assert L == L_yen
            assert not st.truncated
            cohorts += st.iterations
            skipped += st.walks_skipped
        # measured 31 cohorts / 678 skipped walks for this seeded set; a
        # weakened stop rule shows up as extra refine cohorts
        assert cohorts <= 35
        assert skipped > 0

    def test_stepper_accepts_dict_seg_lists(self, net):
        """Out-of-order delivery surface: sending {pair_index: seg_list}
        (any assembly order) equals sending the aligned list."""
        from repro.core.kspdg import ksp_dg_stepper, _partial_ksps

        g, d = net
        s, t = rand_queries(g, 1, seed=59)[0]

        def drive(as_dict):
            stepper = ksp_dg_stepper(d, s, t, 3)
            send = None
            while True:
                try:
                    req = (stepper.send(send) if send is not None
                           else next(stepper))
                except StopIteration as fin:
                    return fin.value
                segs = [
                    _partial_ksps(d, a, b, 3, "pyen", None, req.stats,
                                  req.home)
                    for a, b in req.pairs
                ]
                if as_dict:
                    # deliver in reversed index order to prove tolerance
                    send = {j: segs[j]
                            for j in reversed(range(len(segs)))}
                else:
                    send = segs

        L_list, st_list = drive(False)
        L_dict, st_dict = drive(True)
        assert L_dict == L_list
        assert st_dict.references == st_list.references
