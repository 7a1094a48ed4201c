"""Scheduling: from a request's due time to the wall start of its prefill
(the scheduler handing it to an engine), p90 over the requests due in
the window up to its host end that were admitted."""
from yardstick.cell import percentile


def read(w):
    st = w.stamps
    wait = [st.prefill_start[r] - st.due[r]
            for r in w.due_in_window(w.host_end) if r in st.prefill_start]
    return percentile(wait, 90) if wait else None
