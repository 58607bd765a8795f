"""Spread of a cell's metrics over repeated runs, and the bound it gives.

    python3 bench/spread.py set1/*.out -- set2/*.out

Each file holds one run's output; its last line is the result.  For each
metric and each set: the median, the quartiles as
``statistics.quantiles(values, n=4)`` gives them, and the spread (q3 - q1)
over the median.  The suggested bound is five times the wider spread of
the two sets, and never under 1%.
"""

from __future__ import annotations

import json
import sys

from stats import spread


def results(paths):
    out = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        out.append(json.loads(lines[-1]))
    return out


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    runs = [results(s) for s in sets if s]
    names = sorted({m for rs in runs for r in rs for m in r["metrics"]})
    for name in names:
        rows = []
        for rs in runs:
            vals = [r["metrics"][name]["value"] for r in rs
                    if name in r["metrics"]]
            if len(vals) >= 2:
                rows.append(spread(vals))
        if not rows:
            continue
        widest = max(r[3] for r in rows)
        print(json.dumps({
            "metric": name,
            "sets": [{"median": m, "q1": a, "q3": b, "spread": s}
                     for m, a, b, s in rows],
            "widest_spread": widest,
            "bound_5x": max(0.01, 5 * widest)}))


if __name__ == "__main__":
    main(sys.argv[1:])
