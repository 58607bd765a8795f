"""Reference paths the KSP-DG stepper consumed per query finished in the
window: window deltas of the scheduler's ``references`` and
``completed`` counters."""


def read(win):
    a, b = win.snap_open["scheduler"], win.snap_close["scheduler"]
    if "references" not in b:
        return None
    done = b["completed"] - a["completed"]
    return (b["references"] - a["references"]) / done if done > 0 else None
