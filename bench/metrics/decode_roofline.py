"""Decode step: the least time its steps in the traced window could take
on this chip (the larger of operations over peak and of bytes over HBM
bandwidth: every weight, the live KV at true lengths), over the device
time inside their host spans, in %."""


def read(w):
    tr = w.trace
    if tr is None:
        return None
    dec = tr.inside(w.stamps.decodes)
    busy = tr.device_s(dec)
    if not dec or busy <= 0:
        return None
    return 100 * sum(w.decode_bound_s(ctx) for _, _, ctx in dec) / busy
