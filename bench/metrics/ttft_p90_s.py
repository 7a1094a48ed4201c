"""Time to first token, p90 over every request due in the window: the
wall time from its due time to its first token. A request that never got
its first token counts as longer than any other."""
import math

from yardstick.cell import percentile


def read(w):
    st = w.stamps
    ttft = []
    for rid in w.due_in_window():
        first = st.first_token(rid)
        ttft.append(math.inf if first is None else first - st.due[rid])
    return percentile(ttft, 90) if ttft else None
