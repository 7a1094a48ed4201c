"""Oracles for single-token decode attention: pure-jnp for the dense
split-KV kernel, pure-numpy for the paged kernels, GQA and latent (no jax
in the twins, so a ref mismatch can never share a bug with the
implementation's stack)."""
import math

import jax
import jax.numpy as jnp
import numpy as np


def decode_attention_ref(q, k_cache, v_cache, lengths, *, scale=None):
    """q: [B,H,dh]; caches: [B,Smax,Hkv,dh]; lengths: [B]. -> [B,H,dh]."""
    B, H, dh = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    k = jnp.repeat(k_cache, H // Hkv, axis=2).astype(jnp.float32)
    v = jnp.repeat(v_cache, H // Hkv, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k) * scale
    mask = jnp.arange(Smax)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v).astype(q.dtype)


def decode_attention_paged_ref(q, pool, tables, lengths, *, scale=None):
    """Pure-numpy paged oracle. q: [B,H,dh]; pool: [N,Bs,2*Hkv*dh], each
    row a token's K heads then its V heads; tables: [B,nb]; lengths: [B].
    -> [B,H,dh] (f32 math)."""
    q = np.asarray(q, np.float32)
    pool = np.asarray(pool, np.float32)
    tables = np.asarray(tables)
    lengths = np.asarray(lengths)
    B, H, dh = q.shape
    _, Bs, lanes = pool.shape
    Hkv = lanes // (2 * dh)
    nb = tables.shape[1]
    W = nb * Bs
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    # gather through the table, then split each row into K and V
    rows = pool[tables].reshape(B, W, 2, Hkv, dh)
    k = np.repeat(rows[:, :, 0], H // Hkv, axis=2)
    v = np.repeat(rows[:, :, 1], H // Hkv, axis=2)
    s = np.einsum("bhd,bkhd->bhk", q, k) * scale
    mask = np.arange(W)[None, None, :] < lengths[:, None, None]
    s = np.where(mask, s, -np.inf)
    # a slot of length 0 attends nothing: its output is 0
    m = s.max(axis=-1, keepdims=True)
    s = s - np.where(np.isfinite(m), m, 0.0)
    e = np.where(mask, np.exp(s), 0.0)
    p = e / np.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    return np.einsum("bhk,bkhd->bhd", p, v)


def decode_attention_mla_ref(q, pool, tables, lengths, *, scale,
                             value_lanes):
    """Pure-numpy twin of the latent paged kernel. q: [B,H,lanes]; pool:
    [N,Bs,lanes] latent rows; tables: [B,nb]; lengths: [B]. Keys are whole
    rows, values their first ``value_lanes`` lanes. -> [B,H,value_lanes]
    (f32 math)."""
    q = np.asarray(q, np.float32)
    pool = np.asarray(pool, np.float32)
    tables = np.asarray(tables)
    lengths = np.asarray(lengths)
    B = q.shape[0]
    _, Bs, lanes = pool.shape
    W = tables.shape[1] * Bs
    rows = pool[tables].reshape(B, W, lanes)      # gather through the table
    s = np.einsum("bhc,bwc->bhw", q, rows) * scale
    mask = np.arange(W)[None, None, :] < lengths[:, None, None]
    s = np.where(mask, s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    e = np.where(mask, np.exp(s), 0.0)
    p = e / np.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    return np.einsum("bhw,bwr->bhr", p, rows[..., :value_lanes])
