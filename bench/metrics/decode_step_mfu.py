"""Whole decode step: operations of the decode steps in the traced
window (every layer and the head, at true context lengths) over the
device time inside their host spans times the chip's bf16 peak, in %.
It bounds what any one kernel of the step can gain, and reads on
whatever kernels the step runs."""


def read(w):
    tr = w.trace
    if tr is None:
        return None
    dec = tr.inside(w.stamps.decodes)
    busy = tr.device_s(dec)
    if not dec or busy <= 0:
        return None
    return 100 * w.decode_flops(dec) / (busy * w.peaks["bf16_flops"])
