"""A traced run of one cell, with the device's idle gaps named by the
program's own spans.

    python3 bench/named_trace.py --workload col-s.saturate --seed 7 \
        --seconds 51 --trace 1

runs ``run.py`` unchanged in all it measures and prints, and adds two
things while the profiler records: a clock anchor of the program's
(``obs.clock_anchor()``) right after the trace starts, on every pump
loop, and right before it stops.  After ``run.py``'s result line it
prints one more line on standard error, ``named_trace {json}``:

    anchors, clock_residual_us  the anchors taken, and the largest
                       residual of the fit that lays the program's
                       ``obs.clock`` spans on the profile's nanoseconds
                       (``repro.obs.trace.fit_clock``)
    idle_s             the device's idle time in the traced window
    idle_by_span       idle seconds per name, largest first: each idle
                       instant goes to the innermost program span open
                       there (``repro.obs.trace.name_intervals``;
                       ``queue_wait`` left out, it times a wait), else
                       to the harness's ``tick``/``submit``, else
                       ``other``
    idle_gaps          the ten longest gaps, each named by the span that
                       holds most of it
    throughput_qps     this traced run's own, to set beside an untraced
                       run's for what tracing costs
    span_ms_per_query  host ms per answered query of every program span
                       that started in the window (nested spans overlap)

A program without ``clock_anchor`` runs as plain ``run.py`` does, and
the line is left out.  ``--trace 0`` is plain ``run.py``.
"""

from __future__ import annotations

import json
import sys

import run
import spec
import xtrace

TOP = 10


class AnchoredHooks(run.Hooks):
    """``run.Hooks`` with a clock anchor at each edge of the profiler's
    capture and on every poll inside it."""

    last = None  # the run's hooks, for the reduction below

    def __init__(self, *args):
        super().__init__(*args)
        self.anchor = None
        self.win = None
        AnchoredHooks.last = self

    def recording(self):
        return (self.anchor is not None and self.tracer is not None
                and not self.tracer.stopped)

    def window_open(self, win):
        super().window_open(win)
        self.win = win
        if self.tracer is not None:
            self.anchor = getattr(self.obs, "clock_anchor", None)
        if self.recording():
            self.anchor()

    def poll(self, now):
        if self.recording():
            self.anchor()
        super().poll(now)

    def window_close(self, win):
        if self.recording():
            self.anchor()
        super().window_close(win)


def name_gaps(pd, collector):
    """The ``named_trace`` fields that the profile gives, or None when
    the program took no anchors."""
    brackets = getattr(collector, "anchors", None)
    if not brackets:
        return None
    from repro.obs.trace import ANCHOR, fit_clock, name_intervals, on_profile

    window, marks, anchors, devices = None, [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == xtrace.WINDOW:
                        window = (ev.start_ns, end)
                    elif ev.name in xtrace.HOST_MARKS:
                        marks.append((ev.name, ev.start_ns, end))
                    elif ev.name == ANCHOR:
                        anchors.append(ev.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(ev.start_ns, ev.start_ns
                                     + ev.duration_ns)
                                    for ev in line.events])
    fit = fit_clock(brackets, sorted(anchors))
    spans = on_profile(collector.spans(), fit, window)
    gaps = []
    for ops in devices:
        gaps.extend(xtrace.union_ns(ops, *window)[1])
    labels, totals = name_intervals(gaps, spans, marks)
    n_dev = max(len(devices), 1)
    longest = sorted(zip(labels, gaps), key=lambda x: x[1][1] - x[1][0],
                     reverse=True)
    return {"anchors": len(brackets),
            "clock_residual_us": fit.residual_ns / 1e3,
            "idle_s": sum(e - s for s, e in gaps) / n_dev / 1e9,
            "idle_by_span": [[n, t / n_dev / 1e9] for n, t in
                             sorted(totals.items(), key=lambda x: -x[1])],
            "idle_gaps": [[n, (e - s) / 1e9] for n, (s, e) in
                          longest[:TOP]]}


def main():
    named = {}
    reduce_profile = xtrace.reduce_profile

    def reduce_and_name(pd, *args, **kwargs):
        out = reduce_profile(pd, *args, **kwargs)
        hooks = AnchoredHooks.last
        named["trace"] = name_gaps(pd, hooks.win.collector)
        return out

    run.Hooks = AnchoredHooks
    xtrace.reduce_profile = reduce_and_name
    run.main()

    hooks = AnchoredHooks.last
    if not named.get("trace"):
        return
    win = hooks.win
    done = len(win.completed_in_window())
    per = {}
    for r in win.collector.spans():
        if win.t_open <= r.ts < win.t_close:
            per[r.name] = per.get(r.name, 0.0) + r.dur
    line = dict(named["trace"])
    line["throughput_qps"] = spec.reader({"name": "throughput_qps"}, 0)(win)
    line["span_ms_per_query"] = {
        n: t * 1e3 / done
        for n, t in sorted(per.items(), key=lambda x: -x[1])} if done else {}
    print("named_trace " + json.dumps(line), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
