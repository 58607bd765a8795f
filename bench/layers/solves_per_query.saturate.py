"""Grouped device solves dispatched in the window (the program's
``solve_grouped`` spans) per query answered in the window."""


def read(win):
    spans = win.spans("solve_grouped")
    done = len(win.completed_in_window())
    if spans is None or not done:
        return None
    return len(spans) / done
