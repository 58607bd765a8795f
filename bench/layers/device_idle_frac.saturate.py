"""Share of the traced window in which no operation ran on the device."""


def read(win):
    return None if win.trace is None else win.trace.idle_frac
