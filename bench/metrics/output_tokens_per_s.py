"""Output tokens (first tokens included) emitted inside the window, per
second of the window."""


def read(w):
    n = sum(1 for ts in w.stamps.tokens.values() for t in ts
            if w.t0 <= t < w.t_end)
    return n / w.seconds
