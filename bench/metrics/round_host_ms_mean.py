"""Scheduling: time of each of the program's scheduling rounds
(``serve.round``) in the traced window, outside its prefills, inserts and
decode steps (``serve.prefill``, ``serve.insert``, ``serve.decode``), in
which the chip ran nothing: selection, routing, token bookkeeping, mean,
in ms. Device work that outlasts a step, such as a scatter the traced
run waits for after ``insert`` returns, is not the scheduler's."""
from yardstick import program_trace, xtrace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    host = []
    for rnd in pt.named("serve.round"):
        steps = xtrace.union([(s, e) for _, s, e, _ in
                              pt.inside(rnd, program_trace.STEPS)])
        outside = zip([rnd[1]] + [e for _, e in steps],
                      [s for s, _ in steps] + [rnd[2]])
        host.append(1e3 * sum(pt.idle_s(a, b) for a, b in outside))
    return sum(host) / len(host) if host else None
