"""Host time blocked until a device round's results reach numpy (the
program's ``collect`` spans) in the window per query answered in the
window."""


def read(win):
    spans = win.spans("collect")
    done = len(win.completed_in_window())
    if not spans or not done:
        return None
    return sum(r.dur for r in spans) * 1e3 / done
