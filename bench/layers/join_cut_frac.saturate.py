"""Share of the references joined in the window whose join the cutoff at
the top-k list's k-th distance ended at the root: window deltas of the
scheduler's ``joins_cut`` and ``joins`` counters.  Nothing on a program
without them."""


def read(win):
    a, b = win.snap_open["scheduler"], win.snap_close["scheduler"]
    if "joins_cut" not in b:
        return None
    joins = b["joins"] - a["joins"]
    return (b["joins_cut"] - a["joins_cut"]) / joins if joins > 0 else None
