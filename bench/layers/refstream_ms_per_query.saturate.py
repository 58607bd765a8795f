"""Host time pulling from the KSP-DG reference stream (the program's
``ref_stream`` spans) in the window per query answered in the window."""


def read(win):
    spans = win.spans("ref_stream")
    done = len(win.completed_in_window())
    if not spans or not done:
        return None
    return sum(r.dur for r in spans) * 1e3 / done
