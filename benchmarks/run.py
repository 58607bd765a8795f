"""Run every benchmark (one per paper table/figure).

    PYTHONPATH=src python -m benchmarks.run            # quick mode
    PYTHONPATH=src python -m benchmarks.run --full
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", action="append", default=None)
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from . import (
        bench_baselines,
        bench_batch,
        bench_dtlp,
        bench_engine,
        bench_obs,
        bench_query,
        bench_scaleout,
        bench_update,
        bench_workloads,
    )

    suites = {
        "dtlp": bench_dtlp.main,            # paper Figs 14-15
        "query": bench_query.main,          # paper Fig 16 + iteration figs
        "baselines": bench_baselines.main,  # paper Fig 17
        "scaleout": bench_scaleout.main,    # paper Fig 18
        "engine": bench_engine.main,        # TPU data plane micro-bench
        "batch": bench_batch.main,          # cross-query batched serving
        "update": bench_update.main,        # live-update feed: barrier vs
                                            # streaming epoch handoff
        "obs": bench_obs.main,              # tracing/metrics overhead gate
        "workloads": bench_workloads.main,  # query variants (diverse /
                                            # bounded / one-to-many) on the
                                            # shared scheduler
    }
    t0 = time.time()
    for name, fn in suites.items():
        if args.only and name not in args.only:
            continue
        print(f"\n===== {name} =====", flush=True)
        fn(quick)
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
