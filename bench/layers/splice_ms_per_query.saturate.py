"""Host splice time (the program's ``splice`` spans) in the window per
query answered in the window."""


def read(win):
    spans = win.spans("splice")
    done = len(win.completed_in_window())
    if spans is None or not done:
        return None
    return sum(r.dur for r in spans) * 1e3 / done
