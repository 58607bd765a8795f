"""Cross-query batching throughput: queries/sec vs concurrency, plus
deadline-based (SLO) admission under overload.

The KSPService merges concurrent queries' refine tasks into shared
per-worker grouped solves, so the dense engine's [S, J, z] slab solves
run at multi-query occupancy — per-solve fixed cost (dispatch + jit-call
overhead) amortizes across queries, and cross-query de-dup removes
repeated boundary-pair tasks outright.  This benchmark measures the
effect directly: the same query set served at increasing concurrency on
a fresh service each time (cold worker caches; jit caches warmed by a
prior throwaway run, as in production steady state).

The SLO leg replays a Poisson arrival trace at ~8x the measured service
rate with a tight per-query ``deadline_ms``: admission rejects by
predicted queue delay (tick-latency EWMA × queue depth), and the reject
rate is reported alongside the throughput rows (fig="batch_slo" rows in
``results/bench_batch.json``).

``--mixed`` adds a heterogeneous leg (fig="batch_mixed"): power-law k
and power-law path lengths — mostly small local queries with a heavy
tail of big spans, like real navigation traffic.  Mixed sizes are where
the lockstep tick stalled (every query waited on the slowest cohort's
solve each round); the pipelined scheduler overlaps them, and the rows
report what that buys — p50/p95 latency and peak pipeline occupancy.

``--smoke`` doubles as the CI regression gate: it FAILS (exit 1) when
dense_bf qps at concurrency 8 drops below 90% of concurrency 1 (best of
3 passes each — strict equality would flake on shared-runner noise) —
batching must never cost throughput — or when the mixed leg's p50 at
concurrency 8 exceeds 1.2x concurrency 1: heterogeneous concurrency
must never cost median latency, which is exactly what a re-introduced
lockstep barrier would do.

``--engine`` takes any registered spec — ``--engine pallas_bf`` replays
the same trace through the Pallas ``bf_relax`` backend (interpret-mode
off-TPU; answers are byte-identical to dense_bf, so the rows compare
backend cost on an equal-output footing).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.dtlp import DTLP
from repro.service import KSPService, QueryRequest, ServiceConfig

from .common import build_network, emit, rand_queries, service_row

CONCURRENCIES = [1, 2, 4, 8]


def _config(engine, workers, concurrency, **kw):
    # straggler auto-detection off: a mid-pass re-route would pollute
    # the throughput comparison across concurrency levels
    return ServiceConfig(engine=engine, n_workers=workers,
                         max_in_flight=concurrency,
                         straggler_factor=None, **kw)


def _serve(dtlp, engine, workers, qs, k, concurrency):
    """One timed pass: fresh service (cold caches), warm jit buckets."""
    svc = KSPService(dtlp, _config(engine, workers, concurrency))
    reqs = [QueryRequest(s, t, k) for s, t in qs]
    t0 = time.perf_counter()
    tickets = svc.replay(reqs)
    total = time.perf_counter() - t0
    if not all(tk.result is not None for tk in tickets):
        raise AssertionError("unbounded replay must serve every query")
    return svc, tickets, total


def _mixed_requests(g, n, k_cap=6, seed=11):
    """Power-law mixed workload: k ~ zipf(2.0) clipped to [1, k_cap] and
    path spans ~ zipf(1.5) grid hops — mostly small local queries, a
    heavy tail of big ones."""
    rng = np.random.default_rng(seed)
    side = int(round(np.sqrt(g.n)))
    reqs = []
    for _ in range(n):
        k = int(np.clip(rng.zipf(2.0), 1, k_cap))
        hops = int(np.clip(rng.zipf(1.5), 1, 2 * (side - 1)))
        sr, sc = int(rng.integers(side)), int(rng.integers(side))
        dr = int(rng.integers(hops + 1))
        dc = hops - dr
        tr = int(np.clip(sr + (dr if rng.random() < 0.5 else -dr),
                         0, side - 1))
        tc = int(np.clip(sc + (dc if rng.random() < 0.5 else -dc),
                         0, side - 1))
        s, t = sr * side + sc, tr * side + tc
        if s == t:
            t = tr * side + (tc + 1) % side
        reqs.append(QueryRequest(s, t, k))
    return reqs


def _serve_mixed(dtlp, engine, workers, reqs, concurrency):
    """One timed mixed-size pass (per-request k), fresh service."""
    svc = KSPService(dtlp, _config(engine, workers, concurrency))
    t0 = time.perf_counter()
    tickets = svc.replay(reqs)
    total = time.perf_counter() - t0
    if not all(tk.result is not None for tk in tickets):
        raise AssertionError("unbounded replay must serve every query")
    return svc, tickets, total


def _serve_slo(dtlp, engine, workers, qs, k, concurrency,
               arrival_rate, deadline_ms, seed=7):
    """Overload pass: Poisson arrivals + per-query deadline admission."""
    svc = KSPService(dtlp, _config(engine, workers, concurrency))
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / arrival_rate, size=len(qs))
    arrivals = np.cumsum(gaps)
    reqs = [QueryRequest(s, t, k, deadline_ms=deadline_ms) for s, t in qs]
    svc.replay(reqs, arrival_times=arrivals)
    return svc


def bench_batch(quick=True, engine=None, smoke=False, mixed=False):
    engines = [engine] if engine else ["pyen", "dense_bf"]
    mixed = mixed or smoke  # the CI gate needs the mixed rows
    if smoke:
        g, z = build_network("NY-s", True)
        n_q, workers, k = 6, 2, 3
    else:
        g, z = build_network("NY-s" if quick else "COL-s", quick)
        n_q, workers, k = (32 if quick else 80), 4, 3
    d = DTLP.build(g, z=z, xi=4)
    qs = rand_queries(g, n_q, seed=3)
    repeat = 3 if smoke else 5  # smoke gates on these: one pass flakes
    rows = []
    qps_by_engine: dict = {}
    for eng in engines:
        # warm the shape-bucketed jit solvers at every concurrency level
        # (throwaway services) so timed runs measure steady-state serving
        for c in CONCURRENCIES:
            _serve(d, eng, workers, qs, k, c)
        # best of `repeat` passes per level, each on a fresh (cold-cache)
        # service; repeats INTERLEAVED across levels so slow machine
        # phases (GC, other load) bias every concurrency equally
        best: dict = {}
        for _ in range(repeat):
            for c in CONCURRENCIES:
                run = _serve(d, eng, workers, qs, k, c)
                if c not in best or run[-1] < best[c][-1]:
                    best[c] = run
        for c in CONCURRENCIES:
            svc, tickets, total = best[c]
            st = svc.scheduler.stats
            solves = sum(w.stats.batches for w in svc.cluster.workers)
            lat = sorted(tk.result.latency_ms for tk in tickets)
            qps_by_engine.setdefault(eng, {})[c] = n_q / total
            rows.append(
                dict(
                    fig="batch", engine=eng, concurrency=c, n_queries=n_q,
                    workers=workers, total_s=round(total, 3),
                    qps=round(n_q / total, 2),
                    p50_ms=round(lat[len(lat) // 2], 1),
                    ticks=st.ticks,
                    grouped_solves=solves,
                    tasks_dispatched=st.tasks_dispatched,
                    dedup_frac=round(
                        st.tasks_deduped / max(1, st.tasks_requested), 4
                    ),
                    **service_row(svc),
                )
            )
        # ---- SLO admission under overload (deadline reject rate) ----
        c_top = CONCURRENCIES[-1]
        measured_qps = qps_by_engine[eng][c_top]
        top = next(r for r in rows
                   if r["engine"] == eng and r["concurrency"] == c_top)
        arrival_rate = 8.0 * measured_qps  # ~8x capacity: queue builds
        # tight SLO: the full-burst p50 already contains queueing, so
        # half of it is only reachable from a shallow queue — sustained
        # overload must trip the predicted-delay rejection
        deadline_ms = 0.5 * top["p50_ms"]
        slo_qs = qs * 4  # longer trace: the queue actually saturates
        svc = _serve_slo(d, eng, workers, slo_qs, k, c_top,
                         arrival_rate, deadline_ms)
        served = svc.stats.completed
        rejected = svc.stats.rejected
        rows.append(
            dict(
                fig="batch_slo", engine=eng, concurrency=c_top,
                n_queries=len(slo_qs), workers=workers,
                arrival_rate_qps=round(arrival_rate, 1),
                deadline_ms=round(deadline_ms, 1),
                served=served,
                rejected_deadline=svc.stats.rejected_deadline,
                rejected_queue=svc.stats.rejected_queue,
                reject_rate=round(rejected / len(slo_qs), 4),
                **service_row(svc),
            )
        )
    # ---- mixed-size leg: power-law k / path lengths (fig=batch_mixed) ----
    mixed_p50: dict = {}
    if mixed:
        mreqs = _mixed_requests(g, n_q)
        for eng in engines:
            for c in CONCURRENCIES:  # warm jit buckets per level
                _serve_mixed(d, eng, workers, mreqs, c)
            best = {}
            for _ in range(repeat):
                for c in CONCURRENCIES:
                    run = _serve_mixed(d, eng, workers, mreqs, c)
                    if c not in best or run[-1] < best[c][-1]:
                        best[c] = run
            for c in CONCURRENCIES:
                svc, tickets, total = best[c]
                st = svc.scheduler.stats
                lat = sorted(tk.result.latency_ms for tk in tickets)
                mixed_p50.setdefault(eng, {})[c] = lat[len(lat) // 2]
                rows.append(
                    dict(
                        fig="batch_mixed", engine=eng, concurrency=c,
                        n_queries=len(mreqs), workers=workers,
                        total_s=round(total, 3),
                        qps=round(len(mreqs) / total, 2),
                        p50_ms=round(lat[len(lat) // 2], 1),
                        p95_ms=round(lat[int(len(lat) * 0.95)
                                         - (len(lat) == 1)], 1),
                        # peak dispatched-but-unfinished batches across
                        # all worker pipes (1 would mean lockstep)
                        occupancy=st.max_inflight_batches,
                        dedup_frac=round(
                            st.tasks_deduped / max(1, st.tasks_requested), 4
                        ),
                        **service_row(svc),
                    )
                )
    emit("batch", rows)
    if smoke and "dense_bf" in mixed_p50:
        p1 = mixed_p50["dense_bf"][1]
        p8 = mixed_p50["dense_bf"][CONCURRENCIES[-1]]
        # heterogeneous concurrency must not cost median latency — the
        # signature of a lockstep barrier (every query waiting on the
        # slowest cohort each round) is mixed p50 RISING with concurrency
        if p8 > 1.2 * p1:
            raise SystemExit(
                f"REGRESSION: mixed-workload p50 at concurrency 8 "
                f"({p8:.1f}ms) exceeds 1.2x concurrency 1 ({p1:.1f}ms) — "
                "the pipeline is stalling on mixed query sizes"
            )
        print(f"smoke gate OK: dense_bf mixed p50 {p1:.1f}ms (c=1) → "
              f"{p8:.1f}ms (c=8)")
    if smoke and "dense_bf" in qps_by_engine:
        q1 = qps_by_engine["dense_bf"][1]
        q8 = qps_by_engine["dense_bf"][CONCURRENCIES[-1]]
        # 10% tolerance on best-of-3: a real batching regression is a
        # large effect; strict q8 >= q1 would flake on CI runner noise
        if q8 < 0.9 * q1:
            raise SystemExit(
                f"REGRESSION: dense_bf qps at concurrency 8 ({q8:.2f}) "
                f"fell below concurrency 1 ({q1:.2f}) — cross-query "
                "batching is costing throughput"
            )
        print(f"smoke gate OK: dense_bf qps {q1:.2f} (c=1) → {q8:.2f} (c=8)")
    return rows


def main(quick=True, engine=None, smoke=False, mixed=False):
    bench_batch(quick, engine=engine, smoke=smoke, mixed=mixed)


if __name__ == "__main__":
    import argparse

    from repro.service import available_engines

    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=available_engines(), default=None,
                    help="default: benchmark both engines")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--mixed", action="store_true",
                    help="add the power-law mixed-size leg (fig="
                    "batch_mixed: p50/p95, occupancy)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run that exercises the batched path and "
                    "fails on a c=8-vs-c=1 dense qps regression or a "
                    "mixed-workload p50 latency regression")
    a = ap.parse_args()
    main(quick=not a.full, engine=a.engine, smoke=a.smoke, mixed=a.mixed)
