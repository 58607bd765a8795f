"""Host time turning refined segments into candidates — the scheduler's
``merge`` of segment lists and the stepper's ``join`` into the top-k
list — in the window per query answered in the window."""


def read(win):
    merges, joins = win.spans("merge"), win.spans("join")
    done = len(win.completed_in_window())
    if not merges or not joins or not done:
        return None
    return sum(r.dur for r in merges + joins) * 1e3 / done
