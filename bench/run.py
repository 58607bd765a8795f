"""One benchmark run of one cell: load, warm up, measure, check, report.

    python3 bench/run.py --workload col-s.saturate --seed 7 --seconds 51 \
        --trace 0

run from the root of a checkout.  ``BENCHMARK.json`` names the cell; its
configuration, traffic mix and metric readers are files found by name
(``spec.py``).  The run builds the deployment's road network and the
service over it, offers warm-up traffic, opens the window on the host's
clock, offers the cell's traffic for ``--seconds``, drains, reads peak
device memory, frees the service, and compares every answer given in the
window or drained after it with the plain reference (``oracle.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared, with its
limit.  The same numbers close standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero before any work.

``--pairs-from-seed`` draws the window's queries from ``--seed`` instead
of the mix's fixed ``pool_seed``: the check on fresh pairs that a claimed
gain on the query path must also pass (``PERF.md``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import drive  # noqa: E402
import oracle  # noqa: E402
import roadgen  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

# the first seconds of the window that a traced run records on the
# device; the reduction's cost grows with the trace
TRACE_SECONDS = 10.0


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def require_chips(n):
    """The devices JAX sees, or exit: a TPU with at least ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench: needs {n} TPU chip(s); JAX sees {len(devs)} "
                 f"{devs[0].platform} device(s) ({devs[0].device_kind}); "
                 f"nothing was run")
    return devs


def use_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(spec.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX writes no entry into a directory that is not there
    os.makedirs(path, exist_ok=True)
    # each [S, J, z] bucket is its own program, most compile in under
    # JAX's default 1 s threshold, and every one must be cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCount:
    """Counts, while open, the programs built (each one compiled or
    fetched from the persistent cache) and those fetched from the cache;
    the difference is what compiled afresh."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "built",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "from_cache"}

    def __init__(self):
        import jax

        self.counting = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.counting and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


class Hooks(drive.NoHooks):
    """The window's edges: count compiles, and in a traced run record
    the program's spans and the first seconds of the device trace."""

    def __init__(self, trace, compiles, obs):
        self.trace = trace
        self.compiles = compiles
        self.obs = obs
        self.tracer = None

    def annotate(self, name):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.annotate(name)

    def window_open(self, win):
        self.compiles.counting = True
        if self.trace:
            import xtrace

            win.collector = self.obs.enable(trace=True)
            self.tracer = xtrace.Tracer()
            self.tracer.start()
            self.stop_at = win.t_open + min(TRACE_SECONDS, win.seconds)

    def poll(self, now):
        if self.tracer is not None and not self.tracer.stopped \
                and now >= self.stop_at:
            self.tracer.stop()

    def window_close(self, win):
        self.compiles.counting = False
        win.compiles = dict(self.compiles.counts)
        if self.tracer is not None and not self.tracer.stopped:
            self.tracer.stop()


def serve(api, cfg):
    """The deployment's road network, and the service built over it."""
    from repro.core.graph import Graph

    gspec = dict(cfg["graph"])
    if gspec.pop("directed"):
        raise ValueError("the oracle serves undirected networks only")
    # one network per deployment; the run's seed orders the traffic
    n, us, vs, w0 = roadgen.grid_network(
        traffic.stream(gspec.pop("seed"), "graph"), **gspec)
    t0 = time.perf_counter()
    svc = api.KSPService.build(
        Graph(n, us, vs, w0),
        api.ServiceConfig(**cfg["service"], **cfg["index"]))
    log(f"build {time.perf_counter() - t0:.3f}s | {n} vertices, "
        f"{us.shape[0]} edges")
    return (n, us, vs, w0), svc


def check_answers(graph, w0, todo, pool, chunks=32):
    """(fault or None, gap) per query in ``todo``, judged in a pool."""
    n, us, vs = graph
    parts = [todo[i::chunks] for i in range(chunks)]
    jobs = [(n, us, vs, w0, [(q.s, q.t, q.k,
                              [(float(d), tuple(int(v) for v in p))
                               for d, p in q.result.paths]) for q in part])
            for part in parts if part]
    out = {}
    for part, res in zip([p for p in parts if p],
                         pool.map(oracle.check_group, jobs)):
        for q, r in zip(part, res):
            out[id(q)] = r
    return [out[id(q)] for q in todo]


def judge_run(win, graph, w0, limits, pool):
    """Every answer the run must vouch for, against the reference: each
    one given in the window or drained after it, and each never given.
    No update is offered, so every answer is at the first epoch."""
    due = win.to_judge()
    bad = []
    todo = []
    for q in due:
        if q.result is None:
            bad.append((q, "no answer" if q.rejected is None
                        else f"rejected ({q.rejected})"))
        elif not (q.epoch_sub <= q.result.epoch <= q.epoch_done):
            bad.append((q, f"epoch {q.result.epoch} outside "
                           f"[{q.epoch_sub}, {q.epoch_done}]"))
        elif q.result.truncated:
            bad.append((q, "truncated"))
        else:
            todo.append(q)
    gap = 0.0
    ok = set()
    for q, (fault, g) in zip(todo, check_answers(graph, w0, todo, pool)):
        if fault is not None:
            bad.append((q, fault))
        else:
            gap = max(gap, g)
            if g <= limits["dist_gap"]:
                ok.add(id(q))
    for q, why in bad[:5]:
        log(f"bad answer {q.s}->{q.t} k={q.k} ({q.phase}): {why}")
    checks = {"bad_answers": {"value": len(bad),
                              "limit": limits["bad_answers"]},
              "dist_gap": {"value": gap, "limit": limits["dist_gap"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = sum(1 for q in due if q.result is None)
    return correct, len(due), failed, ok, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--pairs-from-seed", action="store_true")
    args = ap.parse_args()

    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell)
    mix = spec.traffic(cell)
    readers = {m["name"]: (m, spec.reader(m, args.trace))
               for m in spec.metrics(bench, cell, args.trace)}

    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    devs = require_chips(int(cell["chips"]))
    dev = devs[0]
    use_compile_cache()
    compiles = CompileCount()
    from repro import obs
    from repro import service as api

    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)} | "
        f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")

    (n, us, vs, w0), svc = serve(api, cfg)

    warmup_s = float(mix["warmup_seconds"])
    pool_seed = args.seed if args.pairs_from_seed else None
    warm = traffic.Phase(args.seed, "warmup", mix, n, pool_seed)
    main_ph = traffic.Phase(args.seed, "window", mix, n, pool_seed)

    win = drive.Window(args.seconds, float(mix["drain_seconds"]))
    hooks = Hooks(args.trace, compiles, obs)
    off = drive.closed_loop(svc, api, win, warm, main_ph,
                            int(mix["clients"]), warmup_s, hooks)
    win.setup_s = win.t_open - T_START
    t_end = time.perf_counter()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    snap = svc.snapshot()
    log(f"window {win.seconds:.3f}s | drain ended "
        f"{t_end - win.t_close:.3f}s after the close | programs built in "
        f"window {win.compiles} | epoch {snap['epoch']} | "
        f"rebaselines {snap['service']['rebaselines']}")
    if hooks.tracer is not None:
        win.trace = hooks.tracer.reduce(cfg["index"]["z"], dev.device_kind)
        obs.disable()
    del svc, off

    t0 = time.perf_counter()
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(8, os.cpu_count() or 1)) as pool:
        correct, attempted, failed, ok, checks = judge_run(
            win, (n, us, vs), w0, cfg["limits"], pool)
    win.correct_ids = ok
    log(f"reference check of {attempted} answers "
        f"{time.perf_counter() - t0:.3f}s")

    metrics = {}
    for name, (m, read) in readers.items():
        value = read(win)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if win.trace is not None:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        result["breakdown"] = win.trace.breakdown()
    result["checks"] = checks
    for name, m in metrics.items():
        log(f"{name} {m['value']} {m['unit']}")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
