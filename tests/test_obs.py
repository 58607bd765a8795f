"""repro.obs: span API and Chrome-trace export, metrics registry,
flight-recorder rings, and the service-level wiring — one snapshot
schema, cumulative metrics across checkpoint/restore, post-mortem dumps
on exceptions and rejection storms."""

import json

import pytest

from repro import obs
from repro.core.dtlp import DTLP
from repro.data.roadnet import WeightUpdateStream, grid_road_network
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import Record
from repro.service import (
    DeadlineExceeded,
    KSPService,
    QueryRequest,
    ServiceConfig,
    UpdateBatch,
)


@pytest.fixture(autouse=True)
def _obs_reset():
    """obs state is process-global: every test starts and ends disabled."""
    obs.disable()
    yield
    obs.disable()


def build_service(engine="dense_bf", workers=2, seed=2, **cfg_kw):
    g = grid_road_network(10, 10, seed=seed)
    d = DTLP.build(g, z=16, xi=4)
    cfg = ServiceConfig(engine=engine, n_workers=workers,
                        straggler_factor=None, **cfg_kw)
    return g, KSPService(d, cfg)


# --------------------------------------------------------------- span API
class TestSpanAPI:
    def test_nesting_attrs_and_timing(self):
        col = obs.enable(trace=True)
        with obs.span("outer", qid=7) as s:
            s.set(stage="late")
            with obs.span("inner"):
                pass
        # inner exits (and records) first; both carry their attrs
        inner, outer = col.spans("inner")[0], col.spans("outer")[0]
        assert col.events[0].name == "inner"
        assert outer.attrs == {"qid": 7, "stage": "late"}
        # the inner interval nests inside the outer one
        assert outer.ts <= inner.ts
        assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-9

    def test_span_at_records_the_callers_interval(self):
        col = obs.enable(trace=True)
        obs.span_at("solve", 5.0, 2.0, worker=3, k=4)
        (r,) = col.spans("solve")
        assert (r.ts, r.dur) == (5.0, 2.0)
        assert r.tid == 4  # worker attr routes to tid 1 + wid
        assert r.attrs["k"] == 4

    def test_event_is_instant(self):
        col = obs.enable(trace=True)
        obs.event("marker", iteration=1)
        (r,) = col.events
        assert r.kind == "event" and r.dur == 0.0 and r.tid == 0

    def test_worker_scope_sets_ambient_track_and_restores(self):
        col = obs.enable(trace=True)
        obs.event("a")
        with obs.worker_scope(1):
            obs.event("b")
            with obs.worker_scope(0):
                obs.event("c")
            obs.event("d")
        obs.event("e")
        assert [r.tid for r in col.events] == [0, 2, 1, 2, 0]

    def test_explicit_worker_attr_beats_ambient_scope(self):
        col = obs.enable(trace=True)
        with obs.worker_scope(0):
            obs.span_at("x", 0.0, 1.0, worker=5)
        assert col.events[0].tid == 6

    def test_traced_is_late_binding(self):
        @obs.traced()
        def refine(x):
            return x * 2

        assert refine(3) == 6  # disabled: pure passthrough
        col = obs.enable(trace=True)
        assert refine(4) == 8
        (r,) = col.spans()
        assert r.name == refine.__qualname__ and r.name.endswith("refine")

    def test_traced_explicit_name_and_attrs(self):
        col = obs.enable(trace=True)

        @obs.traced("stage", phase="commit")
        def f():
            return 1

        f()
        (r,) = col.spans("stage")
        assert r.attrs["phase"] == "commit"

    def test_span_stamps_error_attr_on_exception(self):
        col = obs.enable(trace=True)
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        assert col.spans("boom")[0].attrs["error"] == "ValueError"


# ----------------------------------------------------------- disabled path
class TestDisabledNoop:
    def test_span_returns_the_singleton(self):
        assert obs.span("a") is obs.span("b") is obs.NOOP_SPAN
        with obs.span("c") as s:
            assert s.set(anything=1) is s  # chainable, still a no-op

    def test_record_calls_are_silent(self):
        obs.span_at("x", 0.0, 1.0, worker=2)
        obs.event("y")
        assert obs.get_collector() is None and not obs.enabled()

    def test_traced_passthrough_preserves_function(self):
        def g(a, b=2):
            """doc"""
            return a + b

        wrapped = obs.traced()(g)
        assert wrapped(1) == 3
        assert wrapped.__name__ == "g" and wrapped.__doc__ == "doc"

    def test_flight_dump_none_and_export_raises(self):
        assert obs.flight_dump("why") is None
        with pytest.raises(RuntimeError, match="not enabled"):
            obs.export("/tmp/never.json")

    def test_enable_disable_round_trip(self):
        col = obs.enable(trace=True)
        obs.event("x")
        assert obs.get_collector() is col and len(col) == 1
        obs.disable()
        obs.event("y")  # dropped, not an error
        assert len(col) == 1


# ------------------------------------------------------ clock alignment
class TestClockAnchor:
    @staticmethod
    def _brackets(n, start=1234.5, step=0.02, width=2e-6):
        mids = [start + i * step for i in range(n)]
        return mids, [(m - width / 2, m + width / 2) for m in mids]

    def test_fit_recovers_offset_and_drift(self):
        mids, brackets = self._brackets(400)
        a, b = 1e9 * (1 + 40e-6), 1.7e18  # 40 ppm drift, epoch offset
        ns = [int(round(b + a * m)) for m in mids]
        fit = obs.fit_clock(brackets, ns)
        assert fit.a == pytest.approx(a, rel=1e-9)
        assert fit.residual_ns < 300  # float64 spacing near 1.7e18 is 256
        for m, y in zip(mids[::37], ns[::37]):
            assert abs(fit.ns(m) - y) < 300
        assert abs(fit.ns(mids[-1] + 1.0) - (b + a * (mids[-1] + 1.0))) < 1e3

    def test_fit_residual_reports_jitter(self):
        mids, brackets = self._brackets(50)
        jitter = [(-1) ** i * 3_000 for i in range(50)]  # +-3 us
        ns = [int(1e9 * m) + 10**15 + j for m, j in zip(mids, jitter)]
        fit = obs.fit_clock(brackets, ns)
        assert 2_500 < fit.residual_ns < 3_500
        assert fit.a == pytest.approx(1e9, rel=1e-6)

    def test_one_anchor_fixes_the_offset(self):
        fit = obs.fit_clock([(10.0, 10.0)], [777])
        assert (fit.a, fit.residual_ns) == (1e9, 0.0)
        assert fit.ns(10.5) == pytest.approx(777 + 5e8)

    def test_count_mismatch_raises(self):
        _, brackets = self._brackets(3)
        with pytest.raises(ValueError, match="3 anchor brackets but 2"):
            obs.fit_clock(brackets, [1, 2])
        with pytest.raises(ValueError):
            obs.fit_clock([], [])

    def test_clock_anchor_records_a_bracket_only_when_enabled(self):
        obs.clock_anchor()  # disabled: nothing to record, no error
        col = obs.enable(trace=True)
        obs.clock_anchor()
        obs.clock_anchor()
        assert len(col.anchors) == 2
        (a0, a1), (b0, b1) = col.anchors
        assert a0 <= a1 <= b0 <= b1
        assert len(col.events) == 0  # anchors are not trace records


# ------------------------------------------- spans on a profile's clock
class TestProfileNaming:
    # idle gaps [0, 100), [200, 600), [700, 1000); a splice holds a
    # ref_stream run, and the caller's tick mark ends at 800
    GAPS = [(0, 100), (200, 600), (700, 1000)]
    TICK = [("tick", 0, 800)]

    def test_gaps_split_by_innermost_span(self):
        spans = [("splice", 250, 500), ("ref_stream", 300, 480)]
        labels, totals = obs.name_intervals(self.GAPS, spans, self.TICK)
        assert labels == ["tick", "ref_stream", "other"]
        assert totals == {"tick": 350, "splice": 70, "ref_stream": 180,
                          "other": 200}
        assert sum(totals.values()) == sum(e - s for s, e in self.GAPS)

    def test_marks_name_what_no_span_covers(self):
        labels, totals = obs.name_intervals(self.GAPS, [], self.TICK)
        assert labels == ["tick", "tick", "other"]
        assert totals == {"tick": 600, "other": 200}
        labels, totals = obs.name_intervals(self.GAPS, [])
        assert labels == ["other"] * 3 and totals == {"other": 800}

    def test_innermost_is_the_latest_started(self):
        from repro.obs.trace import innermost

        pieces = innermost([("solve", 0, 10), ("collect", 2, 5),
                            ("dispatch_round", 6, 8)], [("tick", 0, 12)])
        assert pieces == [(0, 2, "solve"), (2, 5, "collect"),
                          (5, 6, "solve"), (6, 8, "dispatch_round"),
                          (8, 10, "solve"), (10, 12, "tick")]

    def test_on_profile_maps_and_filters(self):
        fit = obs.ClockFit(a=1e9, t0=5.0, ns0=1_000.0, residual_ns=0.0)
        recs = [Record("span", "queue_wait", 5.0, 0.5, 0, {}),
                Record("span", "splice", 5.1, 0.2, 0, {}),
                Record("event", "marker", 5.2, 0.0, 0, {}),
                Record("span", "late", 9.0, 0.1, 0, {})]
        spans = obs.on_profile(recs, fit, window=(0, 2e9))
        ((name, s, e),) = spans
        assert name == "splice"
        assert (s, e) == (pytest.approx(1e8 + 1_000),
                          pytest.approx(3e8 + 1_000))
        assert [n for n, _, _ in obs.on_profile(recs, fit)] == [
            "splice", "late"]

    def test_cpu_profile_round_trip(self, tmp_path):
        """An obs span around a profiler annotation lands around it on
        the profile's clock, within the anchor fit's residual."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData, TraceAnnotation

        col = obs.enable(trace=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            obs.clock_anchor()
            for _ in range(20):
                with obs.span("host_work"):
                    with TraceAnnotation("tick"):
                        time.sleep(0.002)
                obs.clock_anchor()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        pd = ProfileData.from_file(path)
        events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name in ("tick", obs.ANCHOR)]
        anchors = sorted(s for n, s, _ in events if n == obs.ANCHOR)
        ticks = sorted(e for e in events if e[0] == "tick")
        assert len(anchors) == len(col.anchors) == 21
        fit = obs.fit_clock(col.anchors, anchors)
        spans = sorted(obs.on_profile(col.spans(), fit),
                       key=lambda x: x[1])
        assert len(spans) == len(ticks) == 20
        tol = fit.residual_ns + 1_000
        for (name, s, e), (_, ts, te) in zip(spans, ticks):
            assert name == "host_work"
            assert s - tol <= ts and te <= e + tol
        labels, _ = obs.name_intervals([(ts, te) for _, ts, te in ticks],
                                       spans)
        assert set(labels) == {"host_work"}


# ---------------------------------------------------------- chrome export
class TestChromeExport:
    def _capture(self):
        col = obs.enable(trace=True)
        t = col.t0
        obs.span_at("admit", t + 0.001, 0.002, qid=0)
        obs.span_at("dispatch", t + 0.003, 0.001, worker=0)
        obs.span_at("solve", t + 0.004, 0.005, worker=0)
        obs.span_at("splice", t + 0.010, 0.001, qid=0)
        obs.event("marker", iteration=1)
        return col

    def test_schema(self, tmp_path):
        self._capture()
        path = tmp_path / "trace.json"
        n = obs.export(str(path))
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert n == sum(1 for e in events if e["ph"] != "M") == 5
        meta = [e for e in events if e["ph"] == "M"]
        names = {e["args"]["name"] for e in meta
                 if e["name"] == "thread_name"}
        assert {"service", "worker-0"} <= names
        assert any(e["name"] == "process_name" for e in meta)
        last = {}
        for e in events:
            assert e["pid"] == 1 and "tid" in e and "name" in e
            if e["ph"] == "M":
                continue
            assert e["ts"] >= last.get(e["tid"], -1.0)  # monotone per tid
            last[e["tid"]] = e["ts"]
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
            else:
                assert e["ph"] == "i" and e["s"] == "t"
        # worker spans landed on the worker lane, service on tid 0
        by_name = {e["name"]: e for e in events if e["ph"] != "M"}
        assert by_name["solve"]["tid"] == 1
        assert by_name["admit"]["tid"] == 0

    def test_args_are_json_clean(self, tmp_path):
        import numpy as np

        obs.enable(trace=True)
        obs.span_at("x", 0.0, 1.0, n=np.int64(3), w=np.float32(0.5),
                    ids=np.arange(2))
        path = tmp_path / "t.json"
        obs.export(str(path))
        (ev,) = [e for e in json.loads(path.read_text())["traceEvents"]
                 if e["ph"] == "X"]
        assert ev["args"] == {"n": 3, "w": 0.5, "ids": [0, 1]}


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_evicts_fifo(self):
        fr = FlightRecorder(capacity=4)
        for i in range(6):
            fr.record(Record("event", f"e{i}", float(i), 0.0, 0, {}))
        assert fr.recorded == 6
        (ring,) = fr.rings.values()
        # strict FIFO: the two oldest evicted, order preserved
        assert [r.name for r in ring] == ["e2", "e3", "e4", "e5"]

    def test_tracks_are_independent_rings(self):
        fr = FlightRecorder(capacity=2)
        for tid in (0, 1, 1, 1):
            fr.record(Record("event", f"t{tid}", 0.0, 0.0, tid, {}))
        assert len(fr.rings[0]) == 1 and len(fr.rings[1]) == 2

    def test_flight_only_mode_keeps_memory_bounded(self):
        col = obs.enable(trace=False, ring_capacity=3)
        for i in range(10):
            obs.event("e", i=i)
        assert len(col) == 0  # nothing kept for export ...
        dump = obs.flight_dump("test")
        assert dump["recorded"] == 10 and dump["capacity"] == 3
        assert [r["attrs"]["i"] for r in dump["tracks"]["service"]] == \
            [7, 8, 9]  # ... only the bounded recent window
        json.dumps(dump)  # serializable as-is

    def test_dump_track_names_match_trace_mapping(self):
        obs.enable(trace=False)
        obs.event("a")
        obs.span_at("b", 0.0, 1.0, worker=1)
        dump = obs.flight_dump("names")
        assert set(dump["tracks"]) == {"service", "worker-1"}
        assert dump["reason"] == "names"


# ----------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_gauge_merge(self):
        a, b = obs.Counter("c"), obs.Counter("c")
        a.inc(), b.inc(2)
        a.merge(b)
        assert a.snapshot() == 3
        g1, g2 = obs.Gauge("g"), obs.Gauge("g")
        g1.set(5.0), g1.set(2.0), g2.set(3.0)
        g1.merge(g2)
        assert g1.snapshot() == {"value": 3.0, "peak": 5.0}

    def test_histogram_observe_merge_percentile(self):
        h1 = obs.Histogram("h", bounds=(1.0, 10.0, 100.0))
        h2 = obs.Histogram("h", bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0):
            h1.observe(v)
        h2.observe(500.0)
        h1.merge(h2)
        snap = h1.snapshot()
        assert snap["count"] == 4 and snap["counts"] == [1, 2, 0, 1]
        assert snap["min"] == 0.5 and snap["max"] == 500.0
        assert h1.percentile(50) == 10.0
        assert h1.percentile(100) == 500.0  # overflow reports the max
        with pytest.raises(ValueError, match="bounds"):
            h1.merge(obs.Histogram("h", bounds=(1.0, 2.0)))

    def test_histogram_load_round_trips_snapshot(self):
        h = obs.Histogram("h", bounds=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = json.loads(json.dumps(h.snapshot()))
        h2 = obs.Histogram("h", bounds=(1.0, 10.0))
        h2.load(snap)
        assert h2.snapshot() == h.snapshot()
        h2.observe(2.0)
        assert h2.count == 4  # keeps accumulating after restore
        with pytest.raises(ValueError, match="bounds differ"):
            obs.Histogram("h", bounds=(1.0,)).load(snap)

    def test_registry_providers_and_metric_reuse(self):
        reg = obs.MetricsRegistry()
        state = {"done": 0}
        reg.provider("svc", lambda: state)
        assert reg.histogram("lat") is reg.histogram("lat")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("lat")
        reg.counter("n").inc(2)
        state["done"] = 5  # providers are live views
        snap = reg.snapshot()
        assert snap["svc"] == {"done": 5}
        assert snap["metrics"]["n"] == 2
        json.dumps(snap)


# ------------------------------------------------------- service wiring
class TestServiceObs:
    def _run(self, svc, g, n=3, k=3, seed=5):
        import numpy as np

        rng = np.random.default_rng(seed)
        qs = [tuple(map(int, rng.choice(g.n, size=2, replace=False)))
              for _ in range(n)]
        return svc.replay([QueryRequest(s, t, k) for s, t in qs])

    def test_three_query_trace_covers_every_pump_stage(self, tmp_path):
        """The tentpole's acceptance trace: 3 queries through 2 workers
        must land admission/queue-wait/splice on the service track and
        dispatch/solve/execute (+ the backend's solve_grouped) on EVERY
        worker lane."""
        g, svc = build_service(engine="dense_bf", workers=2)
        col = obs.enable(trace=True)
        tickets = self._run(svc, g, n=3)
        assert all(tk.result is not None for tk in tickets)

        by_tid = {}
        for r in col.events:
            by_tid.setdefault(r.tid, set()).add(r.name)
        assert {"admit", "queue_wait", "splice"} <= by_tid[0]
        worker_tids = sorted(t for t in by_tid if t > 0)
        assert worker_tids == [1, 2]  # both workers drew tasks
        for tid in worker_tids:
            assert {"dispatch", "solve", "execute", "solve_grouped"} \
                <= by_tid[tid]
        # ... and the per-query spans carry their qids
        qids = {r.attrs["qid"] for r in col.spans("splice")}
        assert qids == {tk._ticket.qid for tk in tickets}

        # the host phases inside them: reference-stream pulls at
        # admission and in each splice, the joins and segment merges of
        # each splice, and the host's wait on each device round
        def inside(r, outer_names):
            return any(o.tid == r.tid and o.ts <= r.ts
                       and r.ts + r.dur <= o.ts + o.dur + 1e-9
                       for name in outer_names for o in col.spans(name))

        assert {"ref_stream", "join", "merge"} <= by_tid[0]
        for name, outer in (("ref_stream", ("admit", "splice")),
                            ("join", ("splice",)), ("merge", ("splice",)),
                            ("collect", ("solve",))):
            spans = col.spans(name)
            assert spans, name
            assert all(inside(r, outer) for r in spans), name
        for tid in worker_tids:
            assert "collect" in by_tid[tid]
        runs = col.spans("ref_stream")
        assert sum(r.attrs["references"] for r in runs) == sum(
            tk.result.stats.references for tk in tickets)
        assert sum(r.attrs["walks_skipped"] for r in runs) == sum(
            tk.result.stats.walks_skipped for tk in tickets)
        assert all(r.attrs["iteration"] >= 1 for r in col.spans("join"))
        assert "ksp_iteration" not in {r.name for r in col.events}

        path = tmp_path / "t.json"
        assert obs.export(str(path)) == len(col.events)

    def test_join_span_carries_the_join_counters(self):
        """Each ``join`` span carries the heap pops of its joins and the
        joins its cutoff ended at the root; over the spans they sum to
        the queries' own stats."""
        g, svc = build_service(engine="pyen", workers=2)
        col = obs.enable(trace=True)
        tickets = self._run(svc, g, n=6)
        joins = col.spans("join")
        assert joins
        assert all({"joins_cut", "join_pops"} <= set(r.attrs)
                   for r in joins)
        for key in ("joins_cut", "join_pops"):
            assert sum(r.attrs[key] for r in joins) == sum(
                getattr(tk.result.stats, key) for tk in tickets), key
        assert sum(r.attrs["join_pops"] for r in joins) > 0

    def test_streaming_update_emits_epoch_handoff_spans(self):
        g, svc = build_service(update_mode="streaming")
        stream = WeightUpdateStream(g, alpha=0.4, tau=0.5, seed=6)
        col = obs.enable(trace=True)
        svc.update(UpdateBatch(*stream.next_batch()))
        names = {r.name for r in col.events}
        assert {"epoch_prepare", "epoch_commit",
                "prepare_patch", "commit_patch"} <= names
        # the per-worker patch spans land on the worker lanes
        assert {r.tid for r in col.spans("commit_patch")} == {1, 2}
        (commit,) = col.spans("epoch_commit")
        assert commit.attrs["epoch"] == svc.epoch == 1

    def test_snapshot_is_one_json_schema_over_every_layer(self):
        g, svc = build_service(engine="pyen", workers=2)
        self._run(svc, g, n=3)
        snap = svc.snapshot()
        json.dumps(snap)  # the whole point: one json.dump, no leaks
        assert set(snap) >= {"epoch", "service", "scheduler", "workers",
                             "cluster", "metrics"}
        assert snap["service"]["completed"] == 3
        assert snap["scheduler"]["ticks"] > 0
        assert len(snap["workers"]) == 2
        for w in snap["workers"]:
            assert {"wid", "tasks", "resyncs", "alive", "slow",
                    "auto_benched"} <= set(w)
        assert snap["cluster"]["resyncs"] == 0
        assert snap["metrics"]["query_latency_ms"]["count"] == 3

    def test_checkpoint_restores_cumulative_metrics_monotone(self):
        """Format-4 regression: restore then snapshot() must CONTINUE the
        counters and histograms, not restart them from zero."""
        g, svc = build_service(engine="pyen", workers=2, seed=7)
        stream = WeightUpdateStream(g, alpha=0.4, tau=0.5, seed=11)
        svc.update(UpdateBatch(*stream.next_batch()))
        self._run(svc, g, n=3)
        before = svc.snapshot()
        snap = svc.checkpoint()
        assert snap["format"] == 4
        # the service section must survive serialization (str keys etc.)
        snap["service"] = json.loads(json.dumps(snap["service"]))

        svc2 = KSPService.restore(
            snap, lambda: grid_road_network(10, 10, seed=7),
            ServiceConfig(engine="pyen", n_workers=2,
                          straggler_factor=None, z=16, xi=4),
        )
        after0 = svc2.snapshot()
        assert after0["service"] == before["service"]
        assert after0["metrics"]["query_latency_ms"] == \
            before["metrics"]["query_latency_ms"]
        assert after0["metrics"]["update_lag_ms"]["count"] == 1

        self._run(svc2, g, n=2, seed=9)
        after = svc2.snapshot()
        assert after["service"]["completed"] == \
            before["service"]["completed"] + 2
        assert after["metrics"]["query_latency_ms"]["count"] == \
            before["metrics"]["query_latency_ms"]["count"] + 2

    def test_old_format_checkpoint_still_restores(self):
        """A format-3 snapshot (no service section) must load cleanly —
        metrics just start fresh."""
        g, svc = build_service(engine="pyen", workers=2, seed=7)
        snap = svc.checkpoint()
        snap.pop("service")
        snap["format"] = 3
        svc2 = KSPService.restore(
            snap, lambda: grid_road_network(10, 10, seed=7),
            ServiceConfig(engine="pyen", n_workers=2, z=16, xi=4),
        )
        assert svc2.snapshot()["service"]["completed"] == 0

    def test_exception_in_tick_dumps_the_flight_recorder(self, tmp_path):
        path = tmp_path / "dumps.jsonl"
        g, svc = build_service(engine="pyen", workers=2,
                               flight_dump_path=str(path))
        self._run(svc, g, n=1)  # populate the rings
        obs_col = obs.enable(trace=False)
        assert obs_col is obs.get_collector()
        svc.kill(0)
        svc.kill(1)
        svc.submit(QueryRequest(0, g.n - 1, 2))
        with pytest.raises(Exception):
            for _ in range(50):
                svc.tick()
        (dump,) = svc.flight_dumps
        assert dump["reason"].startswith("exception:")
        assert "tracks" in dump and "snapshot" in dump
        assert svc.stats.flight_dumps == 1
        # ... and the dump also landed on disk, one JSON object per line
        (line,) = path.read_text().strip().splitlines()
        assert json.loads(line)["reason"] == dump["reason"]

    def test_deadline_storm_dumps_once(self):
        g, svc = build_service(engine="pyen", workers=2, reject_storm=2)
        obs.enable(trace=False)
        # make the SLO predictor see a long queue: nonzero tick EWMA ×
        # queued depth, the admission signal the storm counter sits on
        svc.scheduler.tick_latency_ewma = 1.0
        svc.submit(QueryRequest(0, g.n - 1, 2))
        svc.submit(QueryRequest(1, g.n - 2, 2))
        for _ in range(3):  # 3 straight rejections, storm threshold 2
            with pytest.raises(DeadlineExceeded):
                svc.submit(QueryRequest(2, g.n - 3, 2, deadline_ms=1.0))
        # exactly ONE dump: at the threshold, not on every rejection
        assert [d["reason"] for d in svc.flight_dumps] == ["deadline_storm"]
        assert svc.stats.rejected_deadline == 3
        # a successful admission resets the streak
        svc.submit(QueryRequest(3, g.n - 4, 2))
        assert svc._deadline_streak == 0

    def test_dumps_are_noop_while_obs_disabled(self):
        g, svc = build_service(engine="pyen", workers=2, reject_storm=1)
        svc.scheduler.tick_latency_ewma = 1.0
        svc.submit(QueryRequest(0, g.n - 1, 2))
        svc.submit(QueryRequest(1, g.n - 2, 2))
        with pytest.raises(DeadlineExceeded):
            svc.submit(QueryRequest(2, g.n - 3, 2, deadline_ms=1.0))
        assert svc.flight_dumps == [] and svc.stats.flight_dumps == 0
