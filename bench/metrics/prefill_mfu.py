"""Prefill step: operations the prefills in the traced window need at
their true prompt lengths, over the device time inside their host spans
times the chip's bf16 peak, in %."""


def read(w):
    tr = w.trace
    if tr is None:
        return None
    pre = tr.inside(w.stamps.prefills)
    busy = tr.device_s(pre)
    if not pre or busy <= 0:
        return None
    return 100 * w.prefill_flops(pre) / (busy * w.peaks["bf16_flops"])
