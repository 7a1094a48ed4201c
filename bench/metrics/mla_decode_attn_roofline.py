"""Kernels: the latent (MLA) decode attention against its roofline. The
least time in which the chip could run the absorbed attention core of the
decode steps in the traced window, over the keys they attend (Σ
``live_tokens`` over the program's ``serve.decode`` spans): the larger of
its operations at the chip's bf16 peak (per key, layer and head, scores
over the whole latent row and values over its c_kv lanes) and its latent
rows' bytes at HBM bandwidth (the family's ``mla_decode_attn_flops`` and
``mla_decode_attn_bytes``). Over the device time of the operations under
the ``attention`` scope inside those spans, in %. A family without latent
attention reads nothing."""
from yardstick import program_trace


def read(w):
    f = w.family
    if not hasattr(f, "mla_decode_attn_flops"):
        return None
    pt = program_trace.of(w)
    if pt is None:
        return None
    dec = pt.named("serve.decode")
    live = sum(c.get("live_tokens", 0) for _, _, _, c in dec)
    attn = sum(pt.scope_s(s, e, "attention") for _, s, e, _ in dec)
    if live <= 0 or attn <= 0:
        return None
    least = max(f.mla_decode_attn_flops(w.dims, live) / w.peaks["bf16_flops"],
                f.mla_decode_attn_bytes(w.dims, live)
                / w.peaks["hbm_bytes_per_s"])
    return 100 * least / attn
