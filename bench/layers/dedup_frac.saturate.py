"""Share of refine tasks the scheduler answered from another query's
identical task: (requested - dispatched) / requested over the window."""


def read(win):
    a, b = win.snap_open["scheduler"], win.snap_close["scheduler"]
    asked = b["tasks_requested"] - a["tasks_requested"]
    sent = b["tasks_dispatched"] - a["tasks_dispatched"]
    return (asked - sent) / asked if asked > 0 else None
