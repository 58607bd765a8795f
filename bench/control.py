"""The controls: plain references put in the program's place, judged
exactly as a run judges the program.  ``bf16`` is the control of record
(the precision below the float32 the configuration states); ``approx``
breaks the stated guarantee in a way a later change might be tempted to;
``stale`` breaks the guarantee that an answer is exact at the epoch it
carries.

    python3 bench/control.py --workload col-s.saturate --seeds 11 12 13 \
        --count 300

For each seed it makes the run's network and the first ``--count``
queries the window offers, answers them with each control, and prints
the numbers ``run.py`` compares, beside their limits.  Where the mix
carries a feed, query ``i`` is stamped with the epoch the feed has
reached after ``i + 1`` of the ``--count`` queries' share of the
window's batches (the warm-up's batches all offered before it), and is
judged at that epoch's weights:

    approx  Yen that takes every path after the second from the first
            path's deviations only (skips the later deviation rounds),
            at the stamped epoch's weights
    bf16    Yen with every weight and sum rounded to bfloat16, at the
            stamped epoch's weights
    stale   exact Yen at the weights of the feed's first epoch (the
            network before any batch), stamped with the later epoch:
            an update acknowledged but never applied

Without a feed every query is at the first epoch, and ``stale`` is the
reference itself.  A control that the limits do not fail on every seed
shows a check that cannot see that fault.  Host code only: it never
touches the device.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os


import oracle
import roadgen
import spec
import traffic


def answer(job):
    """One chunk of control answers judged against the reference."""
    kind, n, us, vs, w_served, w_ref, items = job
    ref = oracle.Net(n, us, vs, w_ref)
    served = oracle.Net(n, us, vs, w_served,
                        rnd=oracle.bf16 if kind == "bf16" else oracle.ident)
    out = []
    for s, t, k in items:
        paths = oracle.yen(served, s, t, k,
                           rounds=1 if kind == "approx" else None)
        out.append(oracle.judge(ref, s, t, k, paths, oracle.yen(ref, s, t, k)))
    return out


def stamped_epochs(mix, w0, count, seconds):
    """(weights at every epoch, the epoch each of ``count`` queries
    carries) for the mix's feed at the run's length; no feed, one
    epoch."""
    up = mix.get("updates")
    if up is None:
        return [w0], [0] * count
    warm = traffic.Feed(up["feed_seed"], "warmup", up, w0,
                        mix["warmup_seconds"])
    window = traffic.Feed(up["feed_seed"], "window", up, w0, seconds)
    weights = traffic.epoch_weights(
        w0, [f.batch(i) for f in (warm, window) for i in range(len(f))])
    return weights, [len(warm) + (i + 1) * len(window) // count
                     for i in range(count)]


def readings(kind, seed, cfg, mix, count, seconds, pool, chunks=32):
    gspec = {k: v for k, v in cfg["graph"].items()
             if k not in ("directed", "seed")}
    n, us, vs, w0 = roadgen.grid_network(
        traffic.stream(cfg["graph"]["seed"], "graph"), **gspec)
    ph = traffic.Phase(seed, "window", mix, n)
    count = min(count, len(ph.s))
    weights, epochs = stamped_epochs(mix, w0, count, seconds)
    by_epoch = {}
    for i in range(count):
        by_epoch.setdefault(epochs[i], []).append(
            (int(ph.s[i]), int(ph.t[i]), int(ph.k[i])))
    size = -(-count // chunks)
    jobs = []
    for epoch, items in sorted(by_epoch.items()):
        served = weights[0] if kind == "stale" else weights[epoch]
        pieces = -(-len(items) // size)
        jobs.extend((kind, n, us, vs, served, weights[epoch], items[i::pieces])
                    for i in range(pieces))
    res = [r for part in pool.map(answer, jobs) for r in part]
    bad = sum(1 for fault, _ in res if fault is not None)
    gap = max((g for fault, g in res if fault is None), default=0.0)
    return {"bad_answers": bad, "dist_gap": gap, "answers": len(res),
            "epochs": len(by_epoch)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--count", type=int, required=True,
                    help="queries of the window to answer, about as many "
                         "as a run judges")
    ap.add_argument("--kinds", nargs="+", default=["approx", "bf16"],
                    choices=["approx", "bf16", "stale"])
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell)
    mix = spec.traffic(cell)
    limits = cfg["limits"]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(8, os.cpu_count() or 1)) as pool:
        for kind in args.kinds:
            for seed in args.seeds:
                r = readings(kind, seed, cfg, mix, args.count,
                             bench["run_seconds"], pool)
                failed = any(r[k] > limits[k] for k in limits)
                print(json.dumps({"control": kind, "workload": cell["name"],
                                  "seed": seed, **r, "limits": limits,
                                  "fails": failed}), flush=True)


if __name__ == "__main__":
    main()
