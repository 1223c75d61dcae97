"""Share of the traced window in which no operation ran on the device."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share
