"""The relaxation kernel's share of its roofline: the least time its
logical work needs (roofline.relax_least_s, summed over the kernel's
calls in the trace) over the device time those calls took."""


def read(win):
    return None if win.trace is None else win.trace.relax_roofline
