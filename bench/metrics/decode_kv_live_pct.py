"""Model step: keys the decode steps attend (each active slot's position
plus its new token, the ``live_tokens`` counter) over the keys their
window gathers (every slot's, empty ones too: ``slots`` times
``window_blocks`` blocks of the deployment's block size), summed over
the program's ``serve.decode`` spans in the traced window, in %."""
from yardstick import program_trace


def read(w):
    pt = program_trace.of(w)
    if pt is None:
        return None
    dec = [c for _, _, _, c in pt.named("serve.decode") if c]
    gathered = sum(c["slots"] * c["window_blocks"] for c in dec)
    if gathered <= 0:
        return None
    live = sum(c["live_tokens"] for c in dec)
    return 100 * live / (gathered * pt.block_size)
