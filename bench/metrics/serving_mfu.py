"""Whole step: operations of every prefill and decode token whose span
lies in the traced window, at true lengths, over the traced window's
length times the chip's bf16 peak, in %."""


def read(w):
    tr = w.trace
    if tr is None:
        return None
    flops = (w.prefill_flops(tr.inside(w.stamps.prefills))
             + w.decode_flops(tr.inside(w.stamps.decodes)))
    return 100 * flops / (tr.window_s * w.peaks["bf16_flops"])
