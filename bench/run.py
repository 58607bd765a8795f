"""One benchmark run of one cell: load, warm up, measure, check, report.

    python3 bench/run.py --workload col-s.saturate --seed 7 --seconds 51 \
        --trace 0

run from the root of a checkout.  ``BENCHMARK.json`` names the cell; its
configuration, traffic mix and metric readers are files found by name
(``spec.py``).  The run builds the deployment's road network and the
service over it, offers warm-up traffic, opens the window on the host's
clock, offers the cell's traffic for ``--seconds``, drains, reads peak
device memory, frees the service, and compares every answer given in the
window or drained after it with the plain reference (``oracle.py``), at
the weights of the epoch the answer carries: the harness replays the
update batches it offered (none where the mix has no feed).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared, with its
limit.  The same numbers close standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero before any work.

``--pairs-from-seed`` draws the window's queries, and the feed's
batches, from ``--seed`` instead of the mix's fixed ``pool_seed`` and
``feed_seed``: the check on fresh pairs that a claimed gain on the query
path must also pass (``PERF.md``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
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
        Graph(n, us, vs, w0.copy()),
        api.ServiceConfig(**cfg["service"], **cfg["index"]))
    log(f"build {time.perf_counter() - t0:.3f}s | {n} vertices, "
        f"{us.shape[0]} edges")
    return (n, us, vs, w0), svc


def check_answers(graph, weights, todo, pool, chunks=32):
    """(fault or None, gap) per query in ``todo``, judged in a pool at
    the weights of the epoch each answer carries (``weights[epoch]``),
    one epoch's answers to a job, split into pieces of at most
    1/``chunks`` of them all."""
    n, us, vs = graph
    by_epoch = {}
    for q in todo:
        by_epoch.setdefault(q.result.epoch, []).append(q)
    size = -(-len(todo) // chunks)
    parts, jobs = [], []
    for epoch, group in sorted(by_epoch.items()):
        pieces = -(-len(group) // size)
        for i in range(pieces):
            part = group[i::pieces]
            parts.append(part)
            jobs.append((n, us, vs, weights[epoch],
                         [(q.s, q.t, q.k,
                           [(float(d), tuple(int(v) for v in p))
                            for d, p in q.result.paths]) for q in part]))
    out = {}
    for part, res in zip(parts, pool.map(oracle.check_group, jobs)):
        for q, r in zip(part, res):
            out[id(q)] = r
    return [out[id(q)] for q in todo]


def feed_checks(win, limits):
    """The feed's own numbers: offered batches whose epoch no tick had
    shown by the drain's end, and the longest time from a batch falling
    due to the first tick at its epoch, a batch never shown counted at
    the drain's end."""
    lags = [(win.t_end if u.visible is None else u.visible) - u.due
            for u in win.updates]
    return {"updates_lost": {
                "value": sum(1 for u in win.updates if u.visible is None),
                "limit": limits["updates_lost"]},
            "update_lag_s": {"value": max(lags, default=0.0),
                             "limit": limits["update_lag_s"]}}


def judge_run(win, graph, w0, limits, pool, feed=False):
    """Every answer the run must vouch for, against the reference: each
    one given in the window or drained after it, and each never given.
    Each answer is judged at the weights of the epoch it carries: ``w0``
    with the update batches the run offered replayed in order, the last
    write to a road winning, as the program's coalescing commits them.
    With a ``feed``, every offered batch must also have become visible
    (``feed_checks``)."""
    due = win.to_judge()
    weights = traffic.epoch_weights(
        w0, [(u.eids, u.new_w) for u in win.updates])
    bad = []
    todo = []
    for q in due:
        if q.result is None:
            bad.append((q, "no answer" if q.rejected is None
                        else f"rejected ({q.rejected})"))
        elif not (q.epoch_sub <= q.result.epoch <= q.epoch_done):
            bad.append((q, f"epoch {q.result.epoch} outside "
                           f"[{q.epoch_sub}, {q.epoch_done}]"))
        elif not 0 <= q.result.epoch < len(weights):
            bad.append((q, f"epoch {q.result.epoch} was never offered"))
        elif q.result.truncated:
            bad.append((q, "truncated"))
        else:
            todo.append(q)
    gap = 0.0
    ok = set()
    for q, (fault, g) in zip(todo, check_answers(graph, weights, todo,
                                                  pool)):
        if fault is not None:
            bad.append((q, fault))
        else:
            gap = max(gap, g)
            if g <= limits["dist_gap"]:
                ok.add(id(q))
    for q, why in bad[:5]:
        log(f"bad answer {q.s}->{q.t} k={q.k} ({q.phase}, epoch "
            f"{None if q.result is None else q.result.epoch}): {why}")
    log(f"answers judged at {len({q.result.epoch for q in todo})} "
        f"distinct epochs of {len(weights)}")
    checks = {"bad_answers": {"value": len(bad),
                              "limit": limits["bad_answers"]},
              "dist_gap": {"value": gap, "limit": limits["dist_gap"]}}
    if feed:
        checks.update(feed_checks(win, limits))
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
    feeds = None
    if "updates" in mix:
        up = mix["updates"]
        feed_seed = up["feed_seed"] if pool_seed is None else pool_seed
        feeds = (traffic.Feed(feed_seed, "warmup", up, w0, warmup_s),
                 traffic.Feed(feed_seed, "window", up, w0, args.seconds))

    win = drive.Window(args.seconds, float(mix["drain_seconds"]))
    hooks = Hooks(args.trace, compiles, obs)
    off = drive.closed_loop(svc, api, win, warm, main_ph,
                            int(mix["clients"]), warmup_s, hooks, feeds)
    win.setup_s = win.t_open - T_START
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    snap = svc.snapshot()
    svc_stats = snap["service"]
    log(f"window {win.seconds:.3f}s | drain ended "
        f"{win.t_end - win.t_close:.3f}s after the close | programs built "
        f"in window {win.compiles} | epoch {snap['epoch']} | "
        f"rebaselines {svc_stats['rebaselines']}")
    if win.updates:
        in_win = sum(1 for u in win.updates if win.in_window(u.due))
        late = sorted(u.offered - u.due for u in win.updates)
        # due -> visible, a batch never visible counted at the drain's end
        lag = sorted((win.t_end if u.visible is None else u.visible) - u.due
                     for u in win.updates if win.in_window(u.due))
        log(f"updates offered {len(win.updates)} ({in_win} due in the "
            f"window; offered late by {late[len(late) // 2] * 1e3:.1f} ms "
            f"median, {late[-1] * 1e3:.1f} ms most) "
            f"| committed {svc_stats['update_batches']} | coalesced "
            f"{svc_stats['coalesced_batches']} | handoff waits "
            f"{svc_stats['handoff_waits']} | never visible "
            f"{sum(1 for u in win.updates if u.visible is None)}")
        if lag:
            log(f"update lag, due to visible, of the batches due in the "
                f"window: median {statistics.median(lag) * 1e3:.1f} ms, "
                f"most {lag[-1] * 1e3:.1f} ms")
    if hooks.tracer is not None:
        win.trace = hooks.tracer.reduce(cfg["index"]["z"], dev.device_kind)
        obs.disable()
    del svc, off

    t0 = time.perf_counter()
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(8, os.cpu_count() or 1)) as pool:
        correct, attempted, failed, ok, checks = judge_run(
            win, (n, us, vs), w0, cfg["limits"], pool, feeds is not None)
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
