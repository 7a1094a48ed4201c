"""KV handoff: time of each of the program's KV inserts
(``serve.insert``) in the traced window in which the chip ran nothing
(block claims, table writes, upload and scatter dispatch), mean, in
ms."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    host = [pt.host_ms(sp) for sp in pt.named("serve.insert")]
    return sum(host) / len(host) if host else None
