"""Tracing pillar of ``repro.obs``: span records and Chrome-trace export.

A *span* is one timed stage — (name, start, duration, track, attrs) —
and an *event* is an instant marker.  The :class:`Collector` accumulates
them and exports the Chrome Trace Event format (the JSON Perfetto and
``chrome://tracing`` load natively), with one *thread* track per worker
and one for the service/scheduler, so a ``serve.py --trace out.json``
run renders the whole pump — admission, queue wait, dispatch, device
solve, host splice, epoch prepare/commit — as parallel per-worker
timelines.

Track mapping (shared with the flight recorder): a record whose attrs
carry ``worker=wid`` lands on tid ``1 + wid``; anything else lands on
the ambient tid (0 = service, or whatever the innermost
``obs.worker_scope(wid)`` set — how backend solve spans, emitted deep
below ``Worker.execute``, inherit the right worker lane without
threading wid through every call).

Timestamps are ``time.perf_counter`` seconds (``obs.clock``), converted
to the format's microseconds at export; everything is sorted by start
time, so per-tid timestamps are monotone in the file.

Clock alignment with a ``jax.profiler`` capture: each
``obs.clock_anchor()`` stores on the collector the ``obs.clock``
bracket around one ``repro.obs.anchor`` ``TraceAnnotation``, and
:func:`fit_clock` fits the profiler's nanoseconds as a line in
``obs.clock`` seconds over every anchor, so a span recorded here lands
on the profile's timeline (drift included) within the fit's residual.
:func:`on_profile` lays a collector's spans there, and
:func:`name_intervals` splits intervals of that timeline (a device's
idle gaps, say) among the innermost spans open over them.
"""

from __future__ import annotations

import bisect
import heapq
import json
import time
from typing import NamedTuple

from .metrics import jsonable
from .recorder import FlightRecorder, track_name

__all__ = ["Record", "Collector", "ClockFit", "fit_clock", "ANCHOR",
           "on_profile", "innermost", "name_intervals"]

#: the name of the profiler annotation ``obs.clock_anchor()`` records
ANCHOR = "repro.obs.anchor"


class Record(NamedTuple):
    """One completed span ("span") or instant event ("event")."""

    kind: str
    name: str
    ts: float  # perf_counter seconds (absolute)
    dur: float  # seconds; 0.0 for events
    tid: int  # 0 = service track, 1 + wid = worker wid
    attrs: dict


class Collector:
    """Accumulates records; exports Chrome-trace JSON + flight dumps.

    ``trace=True`` keeps every record for export (unbounded — a capture
    tool, not an always-on mode); ``trace=False`` is flight-recorder-
    only: records land in the bounded per-track rings and nothing else,
    so memory stays O(capacity × tracks) over an arbitrarily long run.
    """

    def __init__(self, *, trace: bool = True, ring_capacity: int = 512,
                 t0: float | None = None):
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self.trace = bool(trace)
        self.events: list[Record] = []
        # (before, after) obs.clock reads around each anchor annotation
        self.anchors: list[tuple[float, float]] = []
        self.recorder = FlightRecorder(ring_capacity)

    def record(self, kind: str, name: str, ts: float, dur: float,
               tid: int, attrs: dict) -> None:
        """Append one record to the trace (if on) and the flight ring."""
        rec = Record(kind, name, ts, dur, tid, attrs)
        if self.trace:
            self.events.append(rec)
        self.recorder.record(rec)

    def __len__(self) -> int:
        return len(self.events)

    def spans(self, name: str | None = None) -> list[Record]:
        """Collected span records, optionally filtered by name."""
        return [r for r in self.events
                if r.kind == "span" and (name is None or r.name == name)]

    # ------------------------------------------------------------- export
    def chrome_events(self) -> list[dict]:
        """The Chrome Trace Event list: thread-name metadata first, then
        every record as a complete-span ``ph="X"`` (with ``dur``) or
        instant ``ph="i"`` dict, sorted by start time so ``ts`` is
        monotone per tid."""
        tids = sorted({r.tid for r in self.events} | {0})
        out: list[dict] = [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "ksp-service"}},
        ]
        for tid in tids:
            out.append({
                "ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                "args": {"name": track_name(tid)},
            })
            # sort_index pins the service track above the worker lanes
            out.append({
                "ph": "M", "name": "thread_sort_index", "pid": 1,
                "tid": tid, "args": {"sort_index": tid},
            })
        for r in sorted(self.events, key=lambda r: (r.ts, r.tid)):
            ev = {
                "ph": "X" if r.kind == "span" else "i",
                "name": r.name,
                "pid": 1,
                "tid": r.tid,
                "ts": (r.ts - self.t0) * 1e6,  # format wants microseconds
                "args": jsonable(r.attrs),
            }
            if r.kind == "span":
                ev["dur"] = r.dur * 1e6
            else:
                ev["s"] = "t"  # instant scope: thread
            out.append(ev)
        return out

    def export_chrome(self, path: str) -> int:
        """Write the trace to ``path`` (Perfetto/chrome://tracing JSON);
        returns the number of non-metadata events written."""
        events = self.chrome_events()
        with open(path, "w") as f:
            json.dump(
                {"traceEvents": events, "displayTimeUnit": "ms"}, f
            )
        return sum(1 for e in events if e["ph"] != "M")

    def flight_dump(self, reason: str) -> dict:
        """The flight recorder's recent window, timeline-aligned."""
        return self.recorder.dump(reason, t0=self.t0)


class ClockFit(NamedTuple):
    """The line ``profiler_ns = ns0 + a * (t - t0)`` from ``obs.clock``
    seconds ``t`` to a profile's nanoseconds, and the largest distance
    of any anchor from it (``residual_ns``)."""

    a: float  # profiler nanoseconds per obs.clock second
    t0: float  # origin on obs.clock (the first anchor's bracket mid)
    ns0: float  # the line's value at t0
    residual_ns: float

    def ns(self, t: float) -> float:
        return self.ns0 + self.a * (t - self.t0)


def fit_clock(brackets, anchor_ns) -> ClockFit:
    """Least-squares fit of the anchors' profiler ``start_ns`` against
    the midpoints of their ``obs.clock`` brackets, in the order taken.

    The fit is centred on the first anchor, so the large absolute values
    of either clock cost no precision.  One anchor fixes the offset
    only, at the nominal 1e9 ns per second.  Raises ``ValueError`` when
    the counts differ (an anchor lost on either side) or are zero.
    """
    if len(brackets) != len(anchor_ns):
        raise ValueError(f"{len(brackets)} anchor brackets but "
                         f"{len(anchor_ns)} anchor events in the profile")
    if not brackets:
        raise ValueError("no clock anchors to fit")
    t0 = (brackets[0][0] + brackets[0][1]) / 2
    ns0 = anchor_ns[0]
    xs = [(lo + hi) / 2 - t0 for lo, hi in brackets]
    ys = [float(y - ns0) for y in anchor_ns]
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    a = (sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx
         if sxx > 0 else 1e9)
    b = my - a * mx
    residual = max(abs(b + a * x - y) for x, y in zip(xs, ys))
    return ClockFit(a, t0, ns0 + b, residual)


#: spans that time a wait rather than host work, left off the profile
NOT_HOST_WORK = ("queue_wait",)


def on_profile(records, fit: ClockFit, window=None,
               skip=NOT_HOST_WORK) -> list[tuple[str, float, float]]:
    """Span ``records`` as ``(name, start_ns, end_ns)`` on a profile's
    clock through ``fit``, those named in ``skip`` left out; with
    ``window`` ``(lo_ns, hi_ns)``, only those that overlap it."""
    out = []
    for r in records:
        if r.kind != "span" or r.name in skip:
            continue
        s, e = fit.ns(r.ts), fit.ns(r.ts + r.dur)
        if window is None or (e > window[0] and s < window[1]):
            out.append((r.name, s, e))
    return out


def innermost(spans, marks=()) -> list[tuple[float, float, str]]:
    """The timeline cut into disjoint pieces ``(start, end, name)``, each
    named by the innermost span open over it: the latest-starting of
    ``spans``, else the latest-starting of ``marks`` (a lower tier, such
    as a caller's own annotations).  Both are ``(name, start, end)``; on
    one thread spans nest, so the latest-starting open one is the
    innermost.  Time that nothing covers has no piece."""
    tagged = sorted([(s, e, 1, name) for name, s, e in spans]
                    + [(s, e, 0, name) for name, s, e in marks])
    bounds = sorted({t for s, e, _, _ in tagged for t in (s, e)})
    pieces = []
    open_ = []  # heap: the innermost open span on top
    i = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(tagged) and tagged[i][0] <= lo:
            s, e, tier, name = tagged[i]
            heapq.heappush(open_, (-tier, -s, e, name))
            i += 1
        while open_ and open_[0][2] <= lo:
            heapq.heappop(open_)
        if open_:
            pieces.append((lo, hi, open_[0][3]))
    return pieces


def name_intervals(intervals, spans, marks=(), rest="other"):
    """Split each ``(start, end)`` of ``intervals`` among the
    :func:`innermost` spans (else marks) open over it, time under
    neither going to ``rest``.  Returns ``(labels, totals)``: per
    interval the name that holds most of it, and the time per name
    summed over all intervals."""
    pieces = innermost(spans, marks)
    starts = [p[0] for p in pieces]
    labels, totals = [], {}
    for lo, hi in intervals:
        share = {}
        left = hi - lo
        j = max(bisect.bisect_right(starts, lo) - 1, 0)
        while j < len(pieces) and pieces[j][0] < hi:
            s, e, name = pieces[j]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                share[name] = share.get(name, 0) + part
                left -= part
            j += 1
        if left > 0 or not share:
            share[rest] = share.get(rest, 0) + left
        labels.append(max(share, key=share.get))
        for name, t in share.items():
            totals[name] = totals.get(name, 0) + t
    return labels, totals
