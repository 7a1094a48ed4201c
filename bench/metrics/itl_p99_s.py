"""Inter-token latency, p99 over every gap between two consecutive tokens
of one request whose later token came inside the window (wall clock)."""
from yardstick.cell import percentile


def read(w):
    gaps = [b - a for ts in w.stamps.tokens.values()
            for a, b in zip(ts, ts[1:]) if w.t0 <= b < w.t_end]
    return percentile(gaps, 99) if gaps else None
