"""Queries answered in the window, and found right by the reference
check, per second of the window."""


def read(win):
    done = [q for q in win.completed_in_window()
            if id(q) in win.correct_ids]
    return len(done) / win.seconds
