"""Scheduling: requests per decode step, mean over the steps in the
window up to its host end."""


def read(w):
    b = [len(ctx) for s, _, ctx in w.stamps.decodes if w.t0 <= s < w.host_end]
    return sum(b) / len(b) if b else None
