"""Plain float32 forward pass of a DeepSeek-V3 share (latent attention,
sparse experts, leading dense layers).

Written from the published model description (``modeling_deepseek.py``
of deepseek-ai/DeepSeek-V3), independent of the program: token
embedding; per layer an RMS norm, then latent attention in its expanded
form: q = q_b(rms_norm(q_a(x))) split into a no-position part and a rope
part per head; kv_a(x) split into c_kv (RMS-normed) and one rope key
shared by the heads; kv_b(c_kv) gives each head's K no-position part and
V; rope on the rope parts; causal attention with softmax scale
(nope + rope)^-1/2 times YaRN's mscale squared; the output projection
and a residual add. Then an RMS norm and either a SwiGLU MLP (the
leading ``first_k_dense_replace`` layers) or the MoE: sigmoid scores of
the router, the choice reading scores plus the correction bias, groups
ranked by the sum of their best two choices and all but the best
``topk_group`` dropped, the top ``num_experts_per_tok`` of what is left,
weights the scores there normalised and scaled by
``routed_scaling_factor``; the sum over the held experts of weight times
expert SwiGLU, plus the shared expert; a residual add. A final RMS norm
and the output head.

Departures, each the program's too: rope is rotate-half over the rope
lanes (the checkpoints store those lanes interleaved, a fixed permutation
of q_b's and kv_a's rope columns that random weights absorb); groups
left out are masked to -inf (as vLLM; HF masks to 0, which differs only
where a kept choice is negative); only the held experts' part of the
routed sum is computed, the other devices' part being absent on one
device.

It runs one sequence at a time, layer by layer, as ``dense.py`` does,
and shares its float8 control (``quant="fp8"``: inputs of every product,
the router's included, rounded to float8 e4m3) and its head.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense import (BUCKET, HEAD_ROWS, QBLOCK, _embed_rows, _fp8,
                             _mm, _rms, head_stats)

f32 = jnp.float32


def yarn(dims):
    """(inverse frequencies [rope/2], cos/sin scale, softmax mscale) of
    YaRN, in float64 numpy."""
    d, base = dims.rope, dims.rope_theta
    pos_freqs = base ** (np.arange(0, d, 2) / d)
    extra, inter = 1.0 / pos_freqs, 1.0 / (dims.yarn_factor * pos_freqs)

    def corr(rot):
        return (d * math.log(dims.yarn_original / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    lo = max(math.floor(corr(dims.yarn_beta_fast)), 0)
    hi = min(math.ceil(corr(dims.yarn_beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(dims.yarn_factor) + 1.0 \
            if dims.yarn_factor > 1 else 1.0
    return (inv, mscale(dims.yarn_mscale) / mscale(dims.yarn_mscale_all_dim),
            mscale(dims.yarn_mscale_all_dim))


def _rope(x, pos, dims):
    """x [S, h, rope]: rotate the (first half, second half) pairs."""
    inv, cs, _ = yarn(dims)
    ang = pos[:, None].astype(f32) * jnp.asarray(inv, f32)[None, :]
    cos = (jnp.cos(ang) * cs)[:, None, :]
    sin = (jnp.sin(ang) * cs)[:, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, dims, quant):
    S, D = h.shape
    H, R, n, r, dv = dims.heads, dims.kv_lora, dims.nope, dims.rope, \
        dims.v_dim
    pos = jnp.arange(S)
    if dims.q_lora:
        qa = _rms(_mm(h, p["wq_a"], quant), p["q_a_norm"], dims.eps)
        q = _mm(qa, p["wq_b"].reshape(dims.q_lora, H * (n + r)), quant)
    else:
        q = _mm(h, p["wq"].reshape(D, H * (n + r)), quant)
    q = q.reshape(S, H, n + r)
    ckv = _mm(h, p["wkv_a"], quant)
    c = _rms(ckv[:, :R], p["kv_a_norm"], dims.eps)
    k_pe = _rope(ckv[:, None, R:], pos, dims)                    # [S,1,r]
    kv = _mm(c, p["wkv_b"].reshape(R, H * (n + dv)), quant).reshape(
        S, H, n + dv)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], pos, dims)], -1)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (S, H, r))], -1)
    v = kv[..., n:]
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    scale = yarn(dims)[2] ** 2 / math.sqrt(n + r)

    def attend(qb_lo):
        qb, lo = qb_lo
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = lo + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            pr = _fp8(pr, -1)
        return jnp.einsum("hqk,khd->qhd", pr, v)

    nq = S // QBLOCK
    o = jax.lax.map(attend, (q.reshape(nq, QBLOCK, H, n + r),
                             jnp.arange(nq) * QBLOCK))
    return _mm(o.reshape(S, H * dv), p["wo"].reshape(H * dv, D), quant)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def routing(h, router, bias, dims, quant=None):
    """The router's weights over all experts [S, E]: zero off each
    token's chosen experts."""
    s = jax.nn.sigmoid(_mm(h, router, quant))
    choice = s + bias
    S, E = choice.shape
    G = dims.n_group
    best2 = jnp.sort(choice.reshape(S, G, E // G), axis=-1)[..., -2:]
    group = best2.sum(-1)                                        # [S,G]
    g_cut = jnp.sort(group, axis=-1)[:, G - dims.topk_group]
    kept = jnp.repeat(group >= g_cut[:, None], E // G, axis=1)
    choice = jnp.where(kept, choice, -jnp.inf)
    cut = jnp.sort(choice, axis=-1)[:, E - dims.top_k]
    w = jnp.where(choice >= cut[:, None], s, 0.0)
    return w / w.sum(-1, keepdims=True) * dims.routed_scaling


def moe(p, h, dims, quant=None):
    """The MoE FFN of the held experts (``dims.held`` from
    ``dims.held_offset``) plus the shared expert, float32."""
    w = routing(h, p["router"], p["router_bias"], dims, quant)
    y = _swiglu(h, p["shared_wg"], p["shared_wu"], p["shared_wd"], quant)
    for e in range(dims.held):
        we = w[:, dims.held_offset + e]
        y = y + we[:, None] * _swiglu(h, p["wg"][e], p["wu"][e], p["wd"][e],
                                      quant)
    return y


@partial(jax.jit, static_argnames=("dims", "quant", "is_moe"))
def _layer(x, blocks, i, dims, quant: Optional[str], is_moe: bool):
    p = jax.tree.map(lambda a: a[i].astype(f32), blocks)
    x = x + _attention(p, _rms(x, p["attn_norm"], dims.eps), dims, quant)
    h = _rms(x, p["ffn_norm"], dims.eps)
    if is_moe:
        return x + moe(p["moe"], h, dims, quant)
    return x + _swiglu(h, p["wi_gate"], p["wi_up"], p["wo_ffn"], quant)


def hidden(params, dims, tokens: np.ndarray, quant=None):
    """Hidden states before the final norm, float32, one row per token and
    at least HEAD_ROWS rows of padding after them."""
    n = len(tokens)
    S = -(-(n + HEAD_ROWS) // BUCKET) * BUCKET
    toks = np.zeros((S,), np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(params["embed"], jnp.asarray(toks))
        for i in range(dims.dense_layers):
            x = _layer(x, params["dense_blocks"], i, dims, quant, False)
        for i in range(dims.moe_layers):
            x = _layer(x, params["blocks"], i, dims, quant, True)
    return x


def served_gaps(params, dims, prompt, output, *, control=False):
    """Teacher-forced over prompt + served tokens: at each served token,
    the reference's best logit minus its logit for the served token, and
    with ``control`` also minus its logit for the token the fp8 pass puts
    first there. Returns (served gaps, control gaps or None)."""
    prompt = np.asarray(prompt, np.int32)
    output = np.asarray(output, np.int32)
    seq = np.concatenate([prompt, output[:-1]])
    row0 = len(prompt) - 1
    x = hidden(params, dims, seq)
    best, at, _ = head_stats(params, dims, x, row0, output)
    if not control:
        return best - at, None
    xq = hidden(params, dims, seq, quant="fp8")
    _, _, pick = head_stats(params, dims, xq, row0, output, quant="fp8")
    del xq
    _, at_pick, _ = head_stats(params, dims, x, row0, pick)
    return best - at, best - at_pick
