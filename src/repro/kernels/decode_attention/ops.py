"""Jit'd wrappers: the dense split-KV kernel with its cross-split
online-softmax merge, and the paged kernels (GQA and latent)."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel)
from repro.kernels.decode_attention.paged_decode import (
    paged_decode_attention_kernel, paged_latent_decode_kernel)


def _merge_splits(o, m, l):
    """Cross-split online-softmax reduction (splits on axis=2)."""
    m_all = jnp.max(m, axis=2, keepdims=True)                 # [B,Hkv,1,G]
    alpha = jnp.exp(m - m_all)                                # [B,Hkv,S,G]
    l_all = jnp.sum(l * alpha, axis=2)                        # [B,Hkv,G]
    o_all = jnp.sum(o * alpha[..., None], axis=2)             # [B,Hkv,G,dh]
    return o_all / jnp.maximum(l_all, 1e-30)[..., None]


@partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, block_kv: int = 512,
                     interpret: bool = False):
    """q: [B, H, dh]; caches: [B, Smax, Hkv, dh]; lengths: [B] int32.

    Returns [B, H, dh]. H % Hkv == 0; the q-head group is packed into the
    MXU M-dim inside the kernel; split partials are merged here.
    """
    B, H, dh = q.shape
    Hkv = k_cache.shape[2]
    assert H % Hkv == 0
    G = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, Hkv, G, dh)
    o, m, l = decode_attention_kernel(
        qg, k_cache, v_cache, lengths.astype(jnp.int32), scale=scale,
        block_kv=block_kv, interpret=interpret)
    o_all = _merge_splits(o, m, l)
    return o_all.reshape(B, H, dh).astype(q.dtype)


@partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(q, pool_k, pool_v, tables, lengths, *,
                           interpret: bool = False):
    """Paged-layout decode attention. q: [B, H, dh]; pools:
    [N, Bs, Hkv*dh] (kv heads folded into lanes); tables: [B, nb] int32
    block ids in sequence order; lengths: [B] keys to attend (>= 1).

    Returns [B, H, dh]. The kernel reads each slot's live blocks in place
    and normalizes its own output (see paged_decode.py): no merge here.
    """
    B, H, dh = q.shape
    Hkv = pool_k.shape[2] // dh
    assert H % Hkv == 0
    G = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    o = paged_decode_attention_kernel(
        q.reshape(B, Hkv, G, dh), pool_k, pool_v, tables, lengths,
        scale=scale, interpret=interpret)
    return o.reshape(B, H, dh)


@partial(jax.jit, static_argnames=("scale", "value_lanes", "interpret"))
def decode_attention_mla(q, pool, tables, lengths, *, scale: float,
                         value_lanes: int, interpret: bool = False):
    """Paged latent (MLA, absorbed) decode attention. q: [B, H, lanes],
    each head's query folded into the latent space; pool: [N, Bs, lanes]
    latent rows [c_kv | k_pe]; tables: [B, nb] int32; lengths: [B] rows
    to attend (>= 1). Returns [B, H, value_lanes]: the softmax-weighted
    c_kv of each head (see paged_decode.py)."""
    return paged_latent_decode_kernel(q, pool, tables, lengths, scale=scale,
                                      value_lanes=value_lanes,
                                      interpret=interpret)
