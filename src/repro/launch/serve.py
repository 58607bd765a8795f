"""KSP serving driver — the paper's deployment (Fig. 12) end to end:
a dynamic road network, streaming weight updates, concurrent KSP queries
batched across a worker cluster, with failure/straggler injection.

Everything goes through the typed ``repro.service.KSPService`` facade:
argv builds ONE ``ServiceConfig``, queries are ``QueryRequest``s (with
an optional ``--deadline-ms`` SLO that rejects by predicted queue
delay), update batches are ``UpdateBatch``es applied behind the epoch
barrier, and every answer reports the graph epoch that served it.

    PYTHONPATH=src python -m repro.launch.serve --rows 16 --cols 16 \
        --workers 8 --queries 50 --epochs 3 --concurrency 8 --kill 3
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.data.roadnet import WeightUpdateStream, grid_road_network
from repro.engine.backend import PallasBackend
from repro.launch.compile_cache import use_compile_cache
from repro.service import (
    VARIANTS,
    BoundedKSPRequest,
    DiverseKSPRequest,
    KSPService,
    OneToManyRequest,
    QueryRequest,
    ServiceConfig,
    UpdateBatch,
    available_engines,
    available_ref_streams,
    get_engine,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=14)
    ap.add_argument("--cols", type=int, default=14)
    ap.add_argument("--z", type=int, default=24)
    ap.add_argument("--xi", type=int, default=6)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--queries", type=int, default=40, help="per epoch")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--tau", type=float, default=0.5)
    ap.add_argument("--kill", type=int, default=None, help="kill this worker after epoch 1")
    ap.add_argument("--revive", action="store_true",
                    help="revive the killed worker one epoch later "
                    "(its replica re-syncs the missed batch before serving)")
    ap.add_argument(
        "--engine", choices=available_engines(), default="pyen",
        help="refine engine spec: pyen (host Yen), dense_bf (jnp grouped "
        "BF), pallas_bf (fused Pallas kernel; compiled on a TPU, "
        "interpreted on the CPU — identical answers to dense_bf)",
    )
    ap.add_argument(
        "--ref-stream", choices=available_ref_streams(), default=None,
        help="reference-path stream for KSP-DG's filter phase: lazy "
        "(Eppstein-style deviation walks, the engine default — immune to "
        "the corridor-ties truncation mode) or yen (simple-path "
        "fallback); default inherits the engine spec",
    )
    ap.add_argument(
        "--mesh", action="store_true",
        help="route the grouped refine through jax.shard_map over the "
        "device mesh with device-resident sharded slabs (works with any "
        "mesh-capable engine: dense_bf, pallas_bf)",
    )
    ap.add_argument(
        "--distributed", action="store_true",
        help="initialize jax.distributed before building the mesh "
        "(multi-host serving: coordinator from REPRO_COORDINATOR / "
        "JAX_COORDINATOR_ADDRESS + REPRO_NUM_PROCESSES/REPRO_PROCESS_ID, "
        "or platform auto-detection); single-process multi-device needs "
        "only XLA_FLAGS=--xla_force_host_platform_device_count=N",
    )
    ap.add_argument(
        "--variant", choices=VARIANTS, default="ksp",
        help="query workload: ksp (plain top-k), diverse (k mutually "
        "dissimilar paths; --min-dist/--cost-add), bounded (every path "
        "within --stretch of the shortest, at most k), one_to_many (one "
        "source to --targets targets; all variants share the same "
        "scheduler and grouped solves — see docs/workloads.md)",
    )
    ap.add_argument(
        "--stretch", type=float, default=1.2,
        help="bounded: answer = all paths with d ≤ stretch × d₀ (≥ 1)",
    )
    ap.add_argument(
        "--min-dist", type=float, default=0.3,
        help="diverse: required pairwise dissimilarity in (0, 1] — any "
        "two answers share at most 1−min_dist of their edges",
    )
    ap.add_argument(
        "--cost-add", type=float, default=None,
        help="diverse: optional detour cap — no answer costs more than "
        "(1+cost_add) × d₀",
    )
    ap.add_argument(
        "--targets", type=int, default=3,
        help="one_to_many: number of random targets per query",
    )
    ap.add_argument(
        "--concurrency", type=int, default=8,
        help="max in-flight queries per scheduler tick (1 = sequential)",
    )
    ap.add_argument(
        "--batch-window", type=float, default=2.0,
        help="ms to wait for more arrivals before starting an "
        "under-occupied tick (latency-for-throughput knob)",
    )
    ap.add_argument(
        "--arrival-rate", type=float, default=200.0,
        help="Poisson arrival rate, queries/sec on the simulated clock",
    )
    ap.add_argument(
        "--max-queue", type=int, default=0,
        help="bounded admission queue capacity; 0 = unbounded "
        "(overflowing queries are rejected and counted)",
    )
    ap.add_argument(
        "--deadline-ms", type=float, default=0.0,
        help="per-query latency SLO: reject when the predicted queue "
        "delay (tick-latency EWMA × queue depth) exceeds this; 0 disables",
    )
    ap.add_argument(
        "--straggler-factor", type=float, default=8.0,
        help="auto-bench a worker whose task-latency EWMA exceeds this "
        "multiple of the fleet median; 0 disables",
    )
    ap.add_argument(
        "--update-mode", choices=("barrier", "streaming"), default="barrier",
        help="how UpdateBatches land: barrier (freeze admission, drain "
        "in-flight, apply — the reference) or streaming (prepare the next "
        "epoch in shadow buffers, pointer-swap handoff, no drain)",
    )
    ap.add_argument(
        "--rebaseline-drift", type=float, default=0.05,
        help="re-anchor DTLP bounds when mean weight drift exceeds this "
        "(loose bounds blow up KSP-DG iteration counts); 0 disables. "
        "This driver streams heavy updates every epoch, so its default "
        "(0.05) is deliberately more aggressive than ServiceConfig's "
        "general-purpose 0.3",
    )
    ap.add_argument(
        "--trace", metavar="PATH", default=None,
        help="capture a Chrome-trace/Perfetto JSON of the whole run "
        "(admission, queue wait, dispatch, device solve, host splice, "
        "epoch prepare/commit as per-worker timelines) to this path; "
        "open at https://ui.perfetto.dev",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    if args.trace:
        from repro import obs

        obs.enable(trace=True)

    mesh = None
    engine = args.engine
    if args.distributed:
        from repro.launch.mesh import init_distributed

        if init_distributed():
            print(f"jax.distributed initialized: process "
                  f"{jax.process_index()}/{jax.process_count()}, "
                  f"{jax.device_count()} global devices")
        else:
            print("--distributed: no coordinator configured; "
                  "continuing single-process with local devices")
    dev = jax.devices()[0]
    spec = get_engine(engine)
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}"
          + (f" | pallas kernel "
             f"{'interpreted' if spec.backend.interpreted else 'compiled'}"
             if isinstance(spec.backend, PallasBackend) else ""))
    if args.mesh:
        if not spec.supports_mesh:
            meshable = [e for e in available_engines()
                        if get_engine(e).supports_mesh]
            ap.error(
                f"--mesh: engine {engine!r} has no device-mesh path; "
                f"mesh-capable engines: {', '.join(meshable)}"
            )
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        print(f"shard_map refine over a {jax.device_count()}x1 device mesh "
              f"({engine}, device-resident sharded slabs)")

    cfg = ServiceConfig(
        engine=engine,
        n_workers=args.workers,
        max_in_flight=args.concurrency,
        max_queue=args.max_queue if args.max_queue > 0 else None,
        batch_window_ms=args.batch_window,
        z=args.z,
        xi=args.xi,
        mesh=mesh,
        straggler_factor=(args.straggler_factor
                          if args.straggler_factor > 0 else None),
        rebaseline_drift=args.rebaseline_drift,
        ref_stream=args.ref_stream,
        update_mode=args.update_mode,
    )
    g = grid_road_network(args.rows, args.cols, seed=args.seed)
    print(f"road network: {g.n} vertices, {g.m} edges")
    t0 = time.time()
    svc = KSPService.build(g, cfg)
    d = svc.dtlp
    print(
        f"DTLP built in {time.time() - t0:.2f}s: "
        f"{d.partition.n_subgraphs} subgraphs, |G_λ|={d.skeleton.n}, "
        f"{d.stats.n_paths} bounding paths "
        f"(EBP-II {d.stats.ebp_slots} → G-MPTree {d.stats.mptree_slots} slots)"
    )
    stream = WeightUpdateStream(g, alpha=args.alpha, tau=args.tau, seed=1)
    rng = np.random.default_rng(2)
    deadline = args.deadline_ms if args.deadline_ms > 0 else None

    def make_request(rng):
        if args.variant == "one_to_many":
            picks = rng.choice(g.n, size=args.targets + 1, replace=False)
            return OneToManyRequest(
                int(picks[0]), targets=tuple(map(int, picks[1:])),
                k=args.k, deadline_ms=deadline)
        s, t = map(int, rng.choice(g.n, size=2, replace=False))
        if args.variant == "diverse":
            return DiverseKSPRequest(s, t, k=args.k, min_dist=args.min_dist,
                                     cost_add=args.cost_add,
                                     deadline_ms=deadline)
        if args.variant == "bounded":
            return BoundedKSPRequest(s, t, k=args.k, stretch=args.stretch,
                                     deadline_ms=deadline)
        return QueryRequest(s, t, k=args.k, deadline_ms=deadline)

    total_empty = 0
    for epoch_i in range(args.epochs):
        if args.kill is not None and epoch_i == 1:
            svc.kill(args.kill)
            print(f"-- killed worker {args.kill}; replicas take over --")
        if args.kill is not None and args.revive and epoch_i == 2:
            svc.revive(args.kill)
            print(f"-- revived worker {args.kill}; it re-syncs missed "
                  f"update batches before serving --")
        reqs = [make_request(rng) for _ in range(args.queries)]
        gaps = rng.exponential(1.0 / args.arrival_rate, size=args.queries)
        arrivals = svc.scheduler.clock + np.cumsum(gaps)
        # per-epoch reporting: delta the counters, reset the gauges
        st = svc.scheduler.stats
        before = (st.ticks, st.tasks_requested, st.tasks_dispatched)
        rej_before = svc.stats.rejected
        slo_before = svc.stats.rejected_deadline
        st.max_queue_depth = 0
        st.max_in_flight = 0
        tickets = svc.replay(reqs, arrival_times=arrivals)
        served = [tk.result for tk in tickets if tk.result is not None]
        lat = np.array([r.latency_ms for r in served])
        truncated = sum(r.truncated for r in served)
        # empty results are real serving failures (disconnected endpoints
        # or truncation to nothing) — count them explicitly; an `assert`
        # here would be compiled away under `python -O`
        empty = sum(1 for r in served if not r.paths)
        total_empty += empty
        ticks, requested, dispatched = (
            st.ticks - before[0], st.tasks_requested - before[1],
            st.tasks_dispatched - before[2],
        )
        rejected = svc.stats.rejected - rej_before
        print(
            f"epoch {svc.epoch}: {len(served)}/{len(tickets)} queries | "
            f"p50 {np.percentile(lat, 50):6.1f}ms  "
            f"p99 {np.percentile(lat, 99):6.1f}ms | "
            f"ticks {ticks}  "
            f"peak queue {st.max_queue_depth}  "
            f"deduped {requested - dispatched}/{requested} tasks | "
            f"reissued so far: {svc.reissues}"
            + (f" | {truncated} truncated (best-effort)" if truncated else "")
            + (f" | {empty} EMPTY results" if empty else "")
            + (f" | {rejected} rejected "
               f"({svc.stats.rejected_deadline - slo_before} by SLO)"
               if rejected else "")
        )
        t0 = time.perf_counter()
        svc.update(UpdateBatch(*stream.next_batch()))
        dt = time.perf_counter() - t0
        print(
            f"  applied 1 update batch → epoch {svc.epoch} "
            f"({args.update_mode} + index maintenance {dt * 1e3:.1f}ms)"
        )
        if svc.stats.rebaselines:
            drift = d.drift()
            print(f"  drift-triggered rebaselines so far: "
                  f"{svc.stats.rebaselines} (current drift {drift:.3f})")
    if svc.resyncs:
        print(f"stale-replica re-syncs: {svc.resyncs} "
              f"(revived workers replayed missed batches before serving)")
    if args.trace:
        from repro import obs

        n_events = obs.export(args.trace)
        print(f"trace: {n_events} events → {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    if total_empty:
        sys.exit(f"FAILED: {total_empty} queries returned no paths")
    print("serving run complete")


if __name__ == "__main__":
    main()
