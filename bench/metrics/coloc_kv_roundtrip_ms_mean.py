"""KV handoff: the colocated round trip of each prompt's KV through the
host: the program's ``serve.prefill.kv_out`` spans of one prefill
(gather and copy to the host) plus the ``serve.insert.upload`` that puts
it back (the first one after that prefill, in order), mean over the
prefills in the traced window whose insert also lies there, in ms."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    uploads = pt.named("serve.insert.upload")
    trips, i = [], 0
    for pre in pt.named("serve.prefill"):
        out = pt.inside(pre, ("serve.prefill.kv_out",))
        while i < len(uploads) and uploads[i][1] < pre[2]:
            i += 1
        if not out or i == len(uploads):
            continue
        up = uploads[i]
        i += 1
        trips.append(1e3 * (sum(e - s for _, s, e, _ in out)
                            + up[2] - up[1]))
    return sum(trips) / len(trips) if trips else None
