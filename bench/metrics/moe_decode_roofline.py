"""Model step: the MoE layers of the decode steps against their roofline.
The least time in which the chip could run them in the traced window,
from the counters of the program's ``serve.decode`` spans: the larger of
the operations (``expert_tokens`` held-expert passes, and the router and
shared expert of each of the ``moe_tokens`` tokens of a MoE layer) at the
chip's bf16 peak, and the weight bytes (each of the ``experts_touched``
held experts once, and every MoE layer's shared expert and router each
step) at HBM bandwidth (the family's ``moe_decode_flops`` and
``moe_decode_bytes``). Over the device time of the operations under the
``moe`` scope inside those spans, in %. A program or family without
held-expert layers reads nothing."""
from yardstick import program_trace


def read(w):
    f = w.family
    if not hasattr(f, "moe_decode_flops"):
        return None
    pt = program_trace.of(w)
    if pt is None:
        return None
    dec = [sp for sp in pt.named("serve.decode") if "expert_tokens" in sp[3]]
    busy = sum(pt.scope_s(s, e, "moe") for _, s, e, _ in dec)
    if not dec or busy <= 0:
        return None
    flops = f.moe_decode_flops(w.dims,
                               sum(c["expert_tokens"] for *_, c in dec),
                               sum(c["moe_tokens"] for *_, c in dec))
    nbytes = f.moe_decode_bytes(w.dims,
                                sum(c["experts_touched"] for *_, c in dec),
                                len(dec))
    least = max(flops / w.peaks["bf16_flops"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100 * least / busy
