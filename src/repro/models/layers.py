"""Core layers: norms, RoPE, GQA attention (flash-in-XLA), SwiGLU.

Attention comes in three implementations selected by ``attn_impl``:
  - "xla"      : recursive block-causal online-softmax attention. Exact,
                 differentiable, O(S*block) memory, and — unlike naive masked
                 blocking — does not spend FLOPs on fully-masked blocks (the
                 causal triangle is decomposed into rectangles + half-size
                 causal problems, recursively). This is the path the dry-run
                 lowers, so the roofline FLOP/byte numbers are honest.
  - "pallas"   : TPU Pallas flash kernel (kernels/flash_attention).
  - "dense"    : naive masked attention (oracle for tests / tiny smokes).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain

f32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(f32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(f32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=f32) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, yarn):
    """YaRN's inverse frequencies and cos/sin scale (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): frequencies interpolated by
    ``factor`` where a dimension turns fewer than ``beta_slow`` times over
    the original context, kept where it turns more than ``beta_fast``
    times, and ramped linearly between."""
    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    extra = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=f32) - lo) / (hi - lo),
                    0.0, 1.0)
    keep = 1.0 - ramp
    freqs = extra / yarn.factor * (1.0 - keep) + extra * keep

    def mscale(m):
        return 0.1 * m * math.log(yarn.factor) + 1.0 if yarn.factor > 1 \
            else 1.0
    return freqs, mscale(yarn.mscale) / mscale(yarn.mscale_all_dim)


def apply_rope(x, positions, theta: float = 10_000.0, yarn=None):
    """x: [..., S, H, dh]; positions: broadcastable to [..., S]. Rotates
    the (first half, second half) pairs; ``yarn`` (a ``YarnConfig``)
    scales the frequencies as YaRN does."""
    dh = x.shape[-1]
    if yarn is None:
        freqs, ms = rope_frequencies(dh, theta), 1.0          # [dh/2]
    else:
        freqs, ms = yarn_frequencies(dh, theta, yarn)
    angles = positions[..., None].astype(f32) * freqs         # [..., S, dh/2]
    cos = jnp.cos(angles)[..., None, :]                       # [..., S, 1, dh/2]
    sin = jnp.sin(angles)[..., None, :]
    if ms != 1.0:
        cos, sin = cos * ms, sin * ms
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention building blocks (online softmax over KV ranges)


def _attend_block(q, k, v, mask=None, scale=1.0):
    """One dense block. q:[B,Sq,H,d] k,v:[B,Sk,H,d] -> (o, m, l) fp32 stats.

    o is *unnormalized* (sum of exp-weighted v); caller divides by l.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=f32) * scale        # [B,H,Sq,Sk]
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows (m == NEG_INF) contribute nothing
    p = jnp.where((m > 0.5 * NEG_INF)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=f32)                # [B,Sq,H,d] f32
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two online-softmax partials over the same queries."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1.transpose(0, 2, 1)[..., None] + o2 * a2.transpose(0, 2, 1)[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def _full_blocked(q, k, v, scale, block):
    """Rectangular (no-mask) attention, scanned over KV blocks."""
    B, Sk, H, d = k.shape
    nb = max(1, Sk // block)
    if Sk % block != 0 or Sk <= block:
        return _attend_block(q, k, v, scale=scale)
    kb = k.reshape(B, nb, block, H, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, H, d).transpose(1, 0, 2, 3, 4)
    Sq = q.shape[1]
    o0 = jnp.zeros((B, Sq, H, d), f32)
    m0 = jnp.full((B, H, Sq), NEG_INF, f32)
    l0 = jnp.zeros((B, H, Sq), f32)

    def body(carry, kv):
        o, m, l = carry
        kblk, vblk = kv
        ob, mb, lb = _attend_block(q, kblk, vblk, scale=scale)
        return _merge(o, m, l, ob, mb, lb), None

    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), (kb, vb))
    return o, m, l


def _causal_rec(q, k, v, scale, block, q_offset):
    """Recursive causal attention. len(q)==len(k); q_offset==0 here.

    causal(S) = [causal(S/2) on top-left] +
                [full(q_hi, k_lo) merged with causal(S/2) on bottom-right].
    """
    S = q.shape[1]
    if S <= block:
        Sq = q.shape[1]
        pos = jnp.arange(Sq)
        mask = (pos[:, None] >= pos[None, :])[None, None]
        return _attend_block(q, k, v, mask=mask, scale=scale)
    half = S // 2
    q1, q2 = q[:, :half], q[:, half:]
    k1, k2 = k[:, :half], k[:, half:]
    v1, v2 = v[:, :half], v[:, half:]
    o_tl, m_tl, l_tl = _causal_rec(q1, k1, v1, scale, block, 0)
    o_bl, m_bl, l_bl = _full_blocked(q2, k1, v1, scale, block)
    o_br, m_br, l_br = _causal_rec(q2, k2, v2, scale, block, 0)
    o_b, m_b, l_b = _merge(o_bl, m_bl, l_bl, o_br, m_br, l_br)
    o = jnp.concatenate([o_tl, o_b], axis=1)
    m = jnp.concatenate([m_tl, m_b], axis=2)
    l = jnp.concatenate([l_tl, l_b], axis=2)
    return o, m, l


def causal_attention_xla(q, k, v, *, scale=None, block=1024):
    """Exact causal attention, flash-style in pure XLA.

    q,k,v: [B, S, H, dh] (kv already repeated to H heads). Returns [B,S,H,dh].
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, m, l = _causal_rec(q, k, v, scale, block, 0)
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def chunked_prefill_attention_xla(q, k_full, v_full, kv_offset, *,
                                  scale=None, block=1024):
    """Attention for a prefill *chunk*: q is tokens [off, off+Sq); kv_full is
    the cache prefix [0, off+Sq). Prefix part is rectangular (no mask), the
    tail is causal. This is the Sarathi/piggyback chunk compute pattern.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    Sq = q.shape[1]
    k_pre, v_pre = k_full[:, :kv_offset], v_full[:, :kv_offset]
    k_new, v_new = (k_full[:, kv_offset:kv_offset + Sq],
                    v_full[:, kv_offset:kv_offset + Sq])
    o_c, m_c, l_c = _causal_rec(q, k_new, v_new, scale, block, 0)
    if kv_offset > 0:
        o_p, m_p, l_p = _full_blocked(q, k_pre, v_pre, scale, block)
        o_c, m_c, l_c = _merge(o_p, m_p, l_p, o_c, m_c, l_c)
    l_c = jnp.maximum(l_c, 1e-30)
    return (o_c / l_c.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def sliding_window_attention_xla(q, k, v, window: int, *, scale=None):
    """Banded causal attention: each token attends to the previous `window`
    tokens (inclusive of self). Implemented with the 2-chunk local trick:
    chunk size W; each q-chunk attends its own chunk + the previous chunk.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    B, S, H, d = q.shape
    W = window
    if S <= W:
        return causal_attention_xla(q, k, v, scale=scale, block=max(128, W))
    pad = (-S) % W
    if pad:
        zq = jnp.zeros((B, pad, H, d), q.dtype)
        q = jnp.concatenate([q, zq], 1)
        k = jnp.concatenate([k, zq], 1)
        v = jnp.concatenate([v, zq], 1)
    Sp = q.shape[1]
    nc = Sp // W
    qc = q.reshape(B, nc, W, H, d)
    kc = k.reshape(B, nc, W, H, d)
    vc = v.reshape(B, nc, W, H, d)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], 1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], 1)
    k2 = jnp.concatenate([k_prev, kc], 2)                     # [B,nc,2W,H,d]
    v2 = jnp.concatenate([v_prev, vc], 2)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qc, k2,
                   preferred_element_type=f32) * scale        # [B,nc,H,W,2W]
    qpos = jnp.arange(W)[:, None]
    kpos = jnp.arange(2 * W)[None, :] - W
    band = (kpos <= qpos) & (kpos > qpos - W)
    first = jnp.arange(nc) == 0
    valid = band[None, None, None] & ~(first[None, :, None, None, None]
                                       & (kpos < 0)[None, None, None])
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s.astype(f32), axis=-1)
    o = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(v2.dtype), v2,
                   preferred_element_type=f32)
    o = o.reshape(B, Sp, H, d)[:, :S]
    return o.astype(q.dtype)


def dense_attention(q, k, v, *, causal=True, scale=None, window: int = 0,
                    kv_offset: int = 0):
    """Naive masked attention — the oracle for tests."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    Sq, Sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=f32) * scale
    qpos = jnp.arange(Sq)[:, None] + kv_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(f32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def decode_attention_xla(q, k_cache, v_cache, pos, *, scale=None, window: int = 0):
    """Single-token decode: q [B,1,H,dh] vs cache [B,Smax,H,dh]; valid <= pos."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    Smax = k_cache.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                   preferred_element_type=f32) * scale        # [B,H,1,Smax]
    kpos = jnp.arange(Smax)
    mask = kpos[None, None, None, :] <= pos
    if window:
        mask &= kpos[None, None, None, :] > pos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s.astype(f32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache,
                      preferred_element_type=f32).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA head plumbing


def repeat_kv(k, num_heads: int):
    """[B,S,Hkv,dh] -> [B,S,H,dh], sharded over tp so the repeat is local."""
    B, S, Hkv, d = k.shape
    if Hkv == num_heads:
        return k
    rep = num_heads // Hkv
    k = jnp.repeat(k, rep, axis=2)
    return constrain(k, "dp", None, "tp", None)


def expand_kv(k, head_map):
    """[B,S,Hkv,dh] -> [B,S,Hp,dh] via an explicit q-head -> kv-head map.

    Generalizes repeat_kv to padded q heads (padded entries map to kv head 0;
    their garbage output is masked in the o-projection)."""
    B, S, Hkv, d = k.shape
    if head_map.shape[0] == Hkv:
        return k
    out = jnp.take(k, head_map, axis=2)
    return constrain(out, "dp", None, "tp", None)


# ---------------------------------------------------------------------------
# SwiGLU MLP


def swiglu(x, wi_gate, wi_up, wo):
    h = jnp.einsum("bsd,dh->bsh", x, wi_gate)
    u = jnp.einsum("bsd,dh->bsh", x, wi_up)
    h = jax.nn.silu(h.astype(f32)).astype(x.dtype) * u
    h = constrain(h, "dp", None, "tp")
    out = jnp.einsum("bsh,hd->bsd", h, wo)
    return constrain(out, "dp", None, None)
