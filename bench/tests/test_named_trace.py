"""The traced run with named idle gaps: its hooks anchor the program's
clock at each edge of the capture and on every poll, and the reduction
lays the program's spans on the profile, recorded on the CPU."""

import glob
import os
import shutil
import time

import drive
import named_trace
from repro import obs


class _Compiles:
    counting = False
    counts = {}


def test_hooks_anchor_the_capture_and_name_it():
    from jax.profiler import ProfileData

    hooks = named_trace.AnchoredHooks(1, _Compiles(), obs)
    win = drive.Window(5.0, 0.0)
    win.t_open = drive.clock()
    win.t_close = win.t_open + win.seconds
    try:
        hooks.window_open(win)
        for _ in range(5):
            with obs.span("host_work"):
                with hooks.annotate("tick"):
                    time.sleep(0.002)
            hooks.poll(drive.clock())
        hooks.window_close(win)
        assert hooks.tracer.stopped
        # one at the open, one per poll, one before the stop
        assert len(win.collector.anchors) == 7
        (path,) = glob.glob(os.path.join(hooks.tracer.dir, "**",
                                         "*.xplane.pb"), recursive=True)
        named = named_trace.name_gaps(ProfileData.from_file(path),
                                      win.collector)
    finally:
        obs.disable()
        shutil.rmtree(hooks.tracer.dir, ignore_errors=True)
    assert named["anchors"] == 7
    assert 0 <= named["clock_residual_us"] < 1e3
    # no TPU plane on the CPU: no idle gaps to name
    assert named["idle_s"] == 0 and named["idle_gaps"] == []


def test_untraced_run_takes_no_anchors():
    hooks = named_trace.AnchoredHooks(0, _Compiles(), obs)
    win = drive.Window(5.0, 0.0)
    win.t_open = drive.clock()
    hooks.window_open(win)
    hooks.poll(drive.clock())
    hooks.window_close(win)
    assert hooks.tracer is None and win.collector is None


def test_program_without_anchors_names_nothing():
    class Old:
        def spans(self):
            return []

    assert named_trace.name_gaps(None, Old()) is None
