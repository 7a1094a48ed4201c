"""Prefill-to-decode handoff: wall time of ``Engine.insert`` (the KV
scatter into the decode pool, waited for in a traced run), mean over the
inserts in the window up to its host end, in ms."""


def read(w):
    ins = [e - s for s, e in w.stamps.inserts if w.t0 <= s < w.host_end]
    return 1e3 * sum(ins) / len(ins) if ins else None
