"""Chip smoke test: the KSP service's device path end to end on a TPU.

    python chip_smoke.py               # one chip: dense_bf, then pallas_bf
    python chip_smoke.py --four-chips  # only the mesh path, on four chips

The one-chip run builds FLA-s (``grid_road_network(36, 36, seed=0)``,
z=48, the largest network ``benchmarks/common.py`` defines) into one
``KSPService`` per device engine (4 workers and drift-triggered
rebaselining at 0.05, as ``launch/serve`` uses for this traffic;
otherwise the default ``ServiceConfig``).  Each service answers 16
seeded k=3 queries at epoch 0, takes one weight-update batch, and
answers 16 more at epoch 1, then 16 more one at a time for a steady
latency.  Every answer must match host Yen (``core.yen.ksp``) on the full graph at its
epoch, carry that epoch, be neither empty nor truncated, and
``pallas_bf`` must return exactly the bytes ``dense_bf`` does, with its
kernel compiled rather than interpreted.

``--four-chips`` serves the same traffic through ``dense_bf`` and
``pallas_bf`` over a 4x1 device mesh (``ServiceConfig(mesh=...)``) and
compares both with the oracle and with the single-device ``dense_bf``
answers, which the same run computes; every worker's slab must span the
four devices.

Timings and memory go to earlier lines; the last line of standard output
is one JSON object naming the device.  Any failed check raises, so the
exit code is non-zero; without a TPU the script exits before any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ROWS, COLS, Z = 36, 36, 48   # FLA-s (benchmarks/common.py)
N_WORKERS = 4
N_QUERIES = 16
K = 3
ENGINES = ("dense_bf", "pallas_bf")
# the update batch moves half the edges by up to ±50% (mean drift
# 0.125): under ServiceConfig's default 0.3 the skeleton's bounds stay
# that loose and epoch-1 queries run into the 10,000-iteration guard, so
# re-anchor as launch/serve does for the same traffic
REBASELINE_DRIFT = 0.05


def check(ok, msg):
    """Raise on a failed check (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is "
                 f"platform {dev.platform!r} ({dev.device_kind}); "
                 f"nothing was run")
    return dev


class Traffic:
    """The seeded workload and its host oracle, shared by every service:
    the update batch, the query pairs per phase, and host Yen's answers
    on an independent copy of the graph at each epoch."""

    def __init__(self, rows, cols, n_queries):
        import numpy as np

        from repro.core.sssp import graph_view
        from repro.core.yen import ksp
        from repro.data.roadnet import WeightUpdateStream, grid_road_network
        from repro.service import QueryRequest

        self.rows, self.cols = rows, cols
        g = grid_road_network(rows, cols, seed=0)
        self.batch = WeightUpdateStream(
            g, alpha=0.5, tau=0.5, seed=1).next_batch()
        rng = np.random.default_rng(2)

        def requests(n):
            return [QueryRequest(*map(int, rng.choice(g.n, 2, replace=False)),
                                 k=K) for _ in range(n)]

        # phase -> (epoch, requests); "steady" is timed one query at a time
        self.phases = {"epoch0": (0, requests(n_queries)),
                       "epoch1": (1, requests(n_queries)),
                       "steady": (1, requests(n_queries))}
        t0 = time.perf_counter()
        self.want = {}
        for epoch in (0, 1):
            if epoch == 1:
                g.apply_updates(*self.batch)
            view = graph_view(g)
            for name, (e, reqs) in self.phases.items():
                if e == epoch:
                    self.want[name] = [ksp(view, r.s, r.t, r.k) for r in reqs]
        print(f"host oracle: {sum(len(r) for _, r in self.phases.values())} "
              f"Yen queries on {g.n} vertices / {g.m} edges in "
              f"{time.perf_counter() - t0:.3f}s", flush=True)

    def graph(self):
        from repro.data.roadnet import grid_road_network

        return grid_road_network(self.rows, self.cols, seed=0)


def check_answers(label, phase, results, want, epoch):
    check(len(results) == len(want), f"{label} {phase}: answer count")
    for i, (res, ref) in enumerate(zip(results, want)):
        where = f"{label} {phase} query {i}"
        check(res is not None, f"{where}: rejected")
        check(res.epoch == epoch, f"{where}: epoch {res.epoch} != {epoch}")
        check(len(res.paths) > 0, f"{where}: no paths")
        check(not res.truncated, f"{where}: truncated")
        got_d = [d for d, _ in res.paths]
        ref_d = [d for d, _ in ref]
        check(len(got_d) == len(ref_d)
              and all(abs(a - b) <= 1e-6 * max(1.0, abs(b))
                      for a, b in zip(got_d, ref_d)),
              f"{where}: distances {got_d} != host Yen {ref_d}")


def serve(engine, traffic, z, mesh=None):
    """Build one service and drive every phase through it; returns the
    answers per phase and prints the timings."""
    import jax
    import numpy as np

    from repro import obs
    from repro.engine.backend import PallasBackend
    from repro.service import KSPService, ServiceConfig, UpdateBatch

    label = engine + ("@mesh" if mesh is not None else "")
    cfg = ServiceConfig(engine=engine, n_workers=N_WORKERS, z=z, mesh=mesh,
                        rebaseline_drift=REBASELINE_DRIFT)
    t0 = time.perf_counter()
    svc = KSPService.build(traffic.graph(), cfg)
    build_s = time.perf_counter() - t0
    slab_bytes = sum(w.slab.adj_dev.nbytes for w in svc.cluster.workers
                     if w.slab is not None and w.slab.adj_dev is not None)
    check(slab_bytes > 0, f"{label}: no slab on the device")
    if mesh is not None:
        for w in svc.cluster.workers:
            if w.slab is not None:
                n_dev = len(w.slab.adj_dev.sharding.device_set)
                check(n_dev == mesh.size,
                      f"{label}: worker {w.wid} slab spans {n_dev} devices")
    print(f"[{label}] build {build_s:.3f}s | device slabs {slab_bytes} bytes",
          flush=True)

    answers = {}
    # first query: compiles its buckets; the service hands back host
    # values, so the clock closes after the device results were fetched
    r0 = traffic.phases["epoch0"][1][0]
    t0 = time.perf_counter()
    first = svc.query(r0.s, r0.t, r0.k)
    check_answers(label, "first", [first], traffic.want["epoch0"][:1], 0)
    print(f"[{label}] first query {time.perf_counter() - t0:.3f}s "
          f"(compile included)", flush=True)

    for phase in ("epoch0", "epoch1"):
        epoch, reqs = traffic.phases[phase]
        if epoch == 1 and svc.epoch == 0:
            t0 = time.perf_counter()
            svc.update(UpdateBatch(*traffic.batch))
            check(svc.epoch == 1, f"{label}: update left epoch {svc.epoch}")
            print(f"[{label}] update batch {time.perf_counter() - t0:.3f}s "
                  f"({svc.stats.rebaselines} rebaseline)", flush=True)
        t0 = time.perf_counter()
        tickets = svc.replay(reqs)
        wall = time.perf_counter() - t0
        answers[phase] = [tk.result for tk in tickets]
        check_answers(label, phase, answers[phase], traffic.want[phase], epoch)
        print(f"[{label}] {phase}: {len(reqs)} queries replayed in "
              f"{wall:.3f}s", flush=True)

    epoch, reqs = traffic.phases["steady"]
    lat, answers["steady"] = [], []
    for r in reqs:
        t0 = time.perf_counter()
        answers["steady"].append(svc.query(r.s, r.t, r.k))
        lat.append(time.perf_counter() - t0)
    check_answers(label, "steady", answers["steady"], traffic.want["steady"],
                  epoch)

    backend = svc.cluster.spec.backend
    solves = [e for e in obs.get_collector().events
              if e.name == "solve_grouped"
              and e.attrs.get("backend") == backend.name]
    obs.get_collector().events.clear()
    check(solves, f"{label}: no device solve ran")
    mode = ""
    if isinstance(backend, PallasBackend):
        check(not backend.interpreted
              and not any(e.attrs.get("interpret") for e in solves),
              f"{label}: the Pallas kernel ran interpreted")
        mode = " | pallas kernel compiled"
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    print(f"[{label}] steady p50 {float(np.percentile(lat, 50)) * 1e3:.3f}ms over "
          f"{len(lat)} sequential queries | {len(solves)} device solves in "
          f"{len({(e.attrs['S'], e.attrs['J'], e.attrs['z']) for e in solves})}"
          f" [S, J, z] buckets{mode} | peak_bytes_in_use {peak}", flush=True)
    return answers


def same_bytes(label, got, ref):
    for phase in ref:
        a = [r.paths for r in got[phase]]
        b = [r.paths for r in ref[phase]]
        check(a == b, f"{label} {phase}: answers differ from the reference")


def run_one_chip(rows=ROWS, cols=COLS, z=Z, n_queries=N_QUERIES):
    traffic = Traffic(rows, cols, n_queries)
    out = {engine: serve(engine, traffic, z) for engine in ENGINES}
    same_bytes("pallas_bf vs dense_bf", out["pallas_bf"], out["dense_bf"])
    n = sum(len(v) for o in out.values() for k, v in o.items()
            if k != "steady")
    print(f"{n} replayed answers equal host Yen; pallas_bf byte-identical "
          f"to dense_bf", flush=True)


def run_four_chips(rows=ROWS, cols=COLS, z=Z, n_queries=N_QUERIES):
    import jax

    from repro.launch.mesh import make_host_mesh

    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    traffic = Traffic(rows, cols, n_queries)
    single = serve("dense_bf", traffic, z)
    mesh = make_host_mesh(4)
    for engine in ENGINES:
        same_bytes(f"{engine}@mesh vs single-device dense_bf",
                   serve(engine, traffic, z, mesh=mesh), single)
    print("mesh answers equal host Yen and the single-device answers; "
          "every worker slab spans 4 devices", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh path and what it is "
                    "compared with")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import the repro package from "
                 f"{ROOT}/src ({e}); run it from a checkout of the repo")
    cache = use_compile_cache()
    dev = require_tpu()

    import jax

    from repro import obs

    warm = os.path.isdir(cache) and len(os.listdir(cache))
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()} | "
          f"compile cache {cache} ({warm or 0} entries at start)", flush=True)
    obs.enable(trace=True)
    (run_four_chips if args.four_chips else run_one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
