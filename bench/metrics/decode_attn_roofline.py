"""Model step: the decode steps' attention against its roofline. The
least time in which the chip could read the KV those steps attend (Σ
``live_tokens`` over the program's ``serve.decode`` spans in the traced
window, times the KV bytes of one token over all layers, at the chip's
HBM bandwidth), over the device time of the operations under the
``kv_window`` or ``attention`` scope inside those spans, in %. It reads
the same work whatever does it: a gather of each slot's window and XLA
attention over the copy, or a kernel that reads the live blocks in
place. No implementation reads fewer than the live keys, so it stays
under 100%."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    dec = pt.named("serve.decode")
    live = sum(c.get("live_tokens", 0) for _, _, _, c in dec)
    # the two scopes are siblings: no operation lies under both
    attn = sum(pt.scope_s(s, e, "kv_window") + pt.scope_s(s, e, "attention")
               for _, s, e, _ in dec)
    if live <= 0 or attn <= 0:
        return None
    least = live * w.dims.kv_bytes_per_token / w.peaks["hbm_bytes_per_s"]
    return 100 * least / attn
