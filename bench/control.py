"""The controls: plain references put in the program's place, judged
exactly as a run judges the program.  ``bf16`` is the control of record
(the precision below the float32 the configuration states); ``approx``
breaks the stated guarantee in a way a later change might be tempted to.

    python3 bench/control.py --workload col-s.saturate --seeds 11 12 13 \
        --count 300

For each seed it makes the run's network and the first ``--count``
queries the window offers, answers them with each control, and prints
the numbers ``run.py`` compares, beside their limits:

    approx  Yen that takes every path after the second from the first
            path's deviations only (skips the later deviation rounds)
    bf16    Yen with every weight and sum rounded to bfloat16

A control that the limits do not fail on every seed shows a check that
cannot see that fault.  Host code only: it never touches the device.
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
    kind, n, us, vs, w, items = job
    ref = oracle.Net(n, us, vs, w)
    served = oracle.Net(n, us, vs, w,
                        rnd=oracle.bf16 if kind == "bf16" else oracle.ident)
    out = []
    for s, t, k in items:
        paths = oracle.yen(served, s, t, k,
                           rounds=1 if kind == "approx" else None)
        out.append(oracle.judge(ref, s, t, k, paths, oracle.yen(ref, s, t, k)))
    return out


def readings(kind, seed, cfg, mix, count, pool, chunks=32):
    gspec = {k: v for k, v in cfg["graph"].items()
             if k not in ("directed", "seed")}
    n, us, vs, w0 = roadgen.grid_network(
        traffic.stream(cfg["graph"]["seed"], "graph"), **gspec)
    ph = traffic.Phase(seed, "window", mix, n)
    items = [(int(ph.s[i]), int(ph.t[i]), int(ph.k[i]))
             for i in range(min(count, len(ph.s)))]
    jobs = [(kind, n, us, vs, w0, items[i::chunks]) for i in range(chunks)]
    res = [r for part in pool.map(answer, jobs) for r in part]
    bad = sum(1 for fault, _ in res if fault is not None)
    gap = max((g for fault, g in res if fault is None), default=0.0)
    return {"bad_answers": bad, "dist_gap": gap, "answers": len(res)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--count", type=int, required=True,
                    help="queries of the window to answer, about as many "
                         "as a run judges")
    ap.add_argument("--kinds", nargs="+", default=["approx", "bf16"])
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
                r = readings(kind, seed, cfg, mix, args.count, pool)
                failed = any(r[k] > limits[k] for k in limits)
                print(json.dumps({"control": kind, "workload": cell["name"],
                                  "seed": seed, **r, "limits": limits,
                                  "fails": failed}), flush=True)


if __name__ == "__main__":
    main()
