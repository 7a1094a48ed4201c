"""Load generator: how late each request due in the window (up to its
host end, before a profiler starts) was handed to the cluster (release
minus due time), p99, in ms. The cluster polls once per scheduling
round, so a long round shows here."""
from yardstick.cell import percentile


def read(w):
    st = w.stamps
    lag = [(st.released[r] - st.due[r]) * 1e3 for r in w.due_in_window(w.host_end)]
    return percentile(lag, 99) if lag else None
