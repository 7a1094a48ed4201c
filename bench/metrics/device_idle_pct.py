"""Device: share of the traced window in which no operation ran on the
chip, in %."""


def read(w):
    tr = w.trace
    if tr is None:
        return None
    return 100 * (1 - tr.busy_s() / tr.window_s)
