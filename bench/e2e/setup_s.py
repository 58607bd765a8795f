"""Process start to window open: imports, network, DTLP build, slab
placement, warm-up traffic."""


def read(win):
    return win.setup_s
