"""Record the device trace of a traced run and reduce it to metrics.

``Tracer`` runs JAX's profiler with the Python tracer off and the host
tracer at its lowest level, inside one ``bench_window`` annotation, and
lets the driver annotate what the host is doing (``submit``, ``tick``,
``update``).  ``reduce_profile`` turns the ``.xplane.pb`` into a
``DeviceTrace``:

    window     the ``bench_window`` annotation's interval
    busy       the union of the device's ``XLA Ops`` intervals inside the
               window, averaged over the devices traced
    idle_frac  1 - busy / window
    ops        device time per operation, the HLO instruction name with
               its numeric suffix dropped (``%bf_relax.3`` -> bf_relax)
    gaps       the device's idle intervals, each labelled by the
               innermost harness annotation open at its midpoint
    kernel     the relaxation kernel's calls: their [S, J, z] output
               shape, parsed from the HLO text the trace gives each op

The profiler puts host and device events on one clock, in nanoseconds
from the start of the trace.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

from roofline import relax_least_s

WINDOW = "bench_window"
HOST_MARKS = ("submit", "tick", "update")
KERNEL = "bf_relax"
_SHAPE = re.compile(r"=\s*f32\[(\d+),(\d+),(\d+)\]")


def op_name(hlo):
    """``%bf_relax.3 = f32[...] custom-call(...)`` -> ``bf_relax``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def union_ns(intervals, lo, hi):
    """Total length of the union of (start, end) intervals, clipped to
    [lo, hi]; and the idle gaps between them as (start, end)."""
    busy = 0.0
    gaps = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


class DeviceTrace:
    """The reduction of one traced window (see the module docstring)."""

    def __init__(self, window, devices, host_marks, z_logical=None,
                 device_kind=None):
        lo, hi = window
        self.window_ns = hi - lo
        busy, gaps, ops, kernel = [], [], {}, []
        for events in devices:
            b, g = union_ns([(s, s + d) for _, s, d in events], lo, hi)
            busy.append(b)
            gaps.extend(g)
            for name, s, d in events:
                if not lo <= s < hi:
                    continue
                short = op_name(name)
                ops[short] = ops.get(short, 0.0) + d
                if short == KERNEL:
                    m = _SHAPE.search(name)
                    if m:
                        kernel.append((int(m.group(1)), int(m.group(2)), d))
        self.busy_ns = sum(busy) / max(len(busy), 1)
        self.ops = ops
        self.kernel = kernel
        self.gaps = [(e - s, _label(host_marks, (s + e) / 2))
                     for s, e in gaps]
        self.z = z_logical
        self.device_kind = device_kind

    @property
    def window_s(self):
        return self.window_ns / 1e9

    @property
    def busy_s(self):
        return self.busy_ns / 1e9

    @property
    def idle_frac(self):
        return 1.0 - self.busy_ns / self.window_ns if self.window_ns else None

    @property
    def relax_roofline(self):
        """Percent: the least time of the kernel's logical work over the
        device time of its calls; None when no call was traced."""
        spent = sum(d for _, _, d in self.kernel)
        if not spent or self.z is None:
            return None
        least = sum(relax_least_s(S, J, self.z, self.device_kind)
                    for S, J, _ in self.kernel)
        return 100.0 * least / (spent / 1e9)

    def breakdown(self, top=10):
        ops = sorted(self.ops.items(), key=lambda x: -x[1])[:top]
        gaps = sorted(self.gaps, key=lambda x: -x[0])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[n, t / 1e9] for t, n in gaps]}


def _label(marks, t):
    best = None
    for name, s, e in marks:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "other"


def reduce_profile(pd, z_logical=None, device_kind=None):
    """A ``DeviceTrace`` from a ``jax.profiler.ProfileData``."""
    window = None
    marks = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_MARKS:
                        marks.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(ev.name, ev.start_ns, ev.duration_ns)
                                    for ev in line.events])
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return DeviceTrace(window, devices, marks, z_logical, device_kind)


class Tracer:
    """JAX's profiler over a part of the window, in a directory of its
    own that ``reduce`` deletes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.stopped = False
        self._window = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def annotate(self, name):
        if self.stopped:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    def reduce(self, z_logical, device_kind):
        from jax.profiler import ProfileData

        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
            return reduce_profile(ProfileData.from_file(files[0]),
                                  z_logical, device_kind)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
