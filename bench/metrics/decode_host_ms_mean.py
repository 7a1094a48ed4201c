"""Engine host: time of each of the program's ``serve.decode`` spans in
the traced window in which the chip ran nothing (block growth, uploads,
dispatch, sampling), mean, in ms."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    host = [pt.host_ms(sp) for sp in pt.named("serve.decode")]
    return sum(host) / len(host) if host else None
