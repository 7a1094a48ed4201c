"""Model step: share of the decode steps' device time that the gather of
every slot's KV window takes (operations under the program's
``kv_window`` scope), over the device time inside the program's
``serve.decode`` spans in the traced window, in %."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    dec = pt.named("serve.decode")
    busy = sum(pt.device_s(s, e) for _, s, e, _ in dec)
    if busy <= 0:
        return None
    return 100 * sum(pt.scope_s(s, e, "kv_window")
                     for _, s, e, _ in dec) / busy
