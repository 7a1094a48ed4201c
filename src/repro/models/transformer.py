"""Unified decoder: dense GQA / MoE / RWKV6 / hybrid, one scan-over-layers.

Three entry points, each lowered by the dry-run:
  - ``train_loss``  : full-sequence causal LM loss (chunked CE, remat)
  - ``prefill``     : builds the KV cache (or recurrent state) for a prompt
  - ``decode_step`` : one token against an existing cache

All weights are stacked with a leading layer dim and the layer loop is a
single ``lax.scan`` so the HLO stays O(1) in depth (critical for 1T-param
configs and for CPU-host compile times).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rwkv6, ssm
from repro.models.config import ModelConfig
from repro.parallel.sharding import constrain

f32 = jnp.float32


# ---------------------------------------------------------------------------
# Init


def _init_mla_layer(key, cfg: ModelConfig):
    """Latent attention weights, laid out as DeepSeek-V3's projections:
    q_a [D, q_lora] and q_b [q_lora, H, nope+rope] (or wq [D, H, nope+rope]
    without q_lora), kv_a [D, kv_lora+rope], kv_b [kv_lora, H, nope+v]
    (each head's K-nope columns, then its V columns), o [H, v, D]."""
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    qk, dv = cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    dt = cfg.jdtype

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, f32)
                / math.sqrt(fan_in)).astype(dt)
    p = {"attn_norm": jnp.ones((D,), dt),
         "wkv_a": normal(ks[2], (D, cfg.latent_dim), D),
         "kv_a_norm": jnp.ones((R,), dt),
         "wkv_b": normal(ks[3], (R, H, cfg.qk_nope_dim + dv), R),
         "wo": normal(ks[4], (H, dv, D), H * dv * cfg.num_layers)}
    if cfg.q_lora_rank:
        p["wq_a"] = normal(ks[0], (D, cfg.q_lora_rank), D)
        p["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), dt)
        p["wq_b"] = normal(ks[1], (cfg.q_lora_rank, H, qk), cfg.q_lora_rank)
    else:
        p["wq"] = normal(ks[0], (D, H, qk), D)
    return p


def _init_attn_layer(key, cfg: ModelConfig):
    if cfg.mla:
        return _init_mla_layer(key, cfg)
    D, dh = cfg.d_model, cfg.dh
    Hkv = cfg.padded_kv_heads
    Hp = cfg.padded_heads
    ks = jax.random.split(key, 10)
    dt = cfg.jdtype
    s = 1.0 / math.sqrt(D)
    p = {
        "attn_norm": jnp.ones((D,), dt),
        "wq": (jax.random.normal(ks[0], (D, Hp, dh), f32) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (D, Hkv, dh), f32) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (D, Hkv, dh), f32) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (Hp, dh, D), f32) * s / math.sqrt(
            cfg.num_layers)).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hp, dh), dt)
        p["bk"] = jnp.zeros((Hkv, dh), dt)
        p["bv"] = jnp.zeros((Hkv, dh), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _init_ffn_layer(key, cfg: ModelConfig):
    D = cfg.d_model
    dt = cfg.jdtype
    s = 1.0 / math.sqrt(D)
    p = {"ffn_norm": jnp.ones((D,), dt)}
    if cfg.moe is not None:
        m = cfg.moe
        ks = jax.random.split(key, 7)
        sh = 1.0 / math.sqrt(D)
        E = m.held_count
        p["moe"] = {
            "router": (jax.random.normal(ks[0], (D, m.num_experts), f32)
                       * 0.02).astype(f32),
            "wg": (jax.random.normal(ks[1], (E, D, m.d_ff_expert),
                                     f32) * sh).astype(dt),
            "wu": (jax.random.normal(ks[2], (E, D, m.d_ff_expert),
                                     f32) * sh).astype(dt),
            "wd": (jax.random.normal(ks[3], (E, m.d_ff_expert, D),
                                     f32) * sh / math.sqrt(cfg.num_layers)
                   ).astype(dt),
        }
        if m.router == "sigmoid":
            p["moe"]["router_bias"] = jnp.zeros((m.num_experts,), f32)
        if m.num_shared_experts:
            F = m.d_ff_expert * m.num_shared_experts
            p["moe"]["shared_wg"] = (jax.random.normal(ks[4], (D, F), f32)
                                     * sh).astype(dt)
            p["moe"]["shared_wu"] = (jax.random.normal(ks[5], (D, F), f32)
                                     * sh).astype(dt)
            p["moe"]["shared_wd"] = (jax.random.normal(ks[6], (F, D), f32)
                                     * sh).astype(dt)
    else:
        ks = jax.random.split(key, 3)
        F = cfg.d_ff
        p["wi_gate"] = (jax.random.normal(ks[0], (D, F), f32) * s).astype(dt)
        p["wi_up"] = (jax.random.normal(ks[1], (D, F), f32) * s).astype(dt)
        p["wo_ffn"] = (jax.random.normal(ks[2], (F, D), f32) * s
                       / math.sqrt(cfg.num_layers)).astype(dt)
    return p


def _init_layer(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.block == "rwkv":
        return rwkv6.init_rwkv_block(k1, cfg)
    p = _init_attn_layer(k1, cfg)
    p.update(_init_ffn_layer(k2, cfg))
    if cfg.block == "hybrid":
        p["ssm"] = ssm.init_ssm(k3, cfg)
        p["attn_out_norm"] = jnp.ones((cfg.d_model,), cfg.jdtype)
        p["ssm_out_norm"] = jnp.ones((cfg.d_model,), cfg.jdtype)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    kE, kL, kH, kV = jax.random.split(key, 4)
    D, V = cfg.d_model, cfg.padded_vocab
    dt = cfg.jdtype
    layer_keys = jax.random.split(kL, cfg.num_layers)
    p = {"embed": (jax.random.normal(kE, (V, D), f32) * 0.02).astype(dt)}
    for name, lcfg, lo, n in layer_stacks(cfg):
        p[name] = jax.vmap(partial(_init_layer, cfg=lcfg))(
            layer_keys[lo:lo + n] if n != cfg.num_layers else layer_keys)
    p["final_norm"] = jnp.ones((D,), dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(kH, (D, V), f32)
                        / math.sqrt(D)).astype(dt)
    if cfg.frontend == "vision":
        vd = cfg.vision_dim
        k1, k2 = jax.random.split(kV)
        p["vis_proj"] = {
            "w1": (jax.random.normal(k1, (vd, D), f32) / math.sqrt(vd)).astype(dt),
            "w2": (jax.random.normal(k2, (D, D), f32) / math.sqrt(D)).astype(dt),
        }
    return p


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of params — no allocation (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Embedding / head


def embed_tokens(params, cfg: ModelConfig, tokens):
    out = params["embed"][tokens]
    return constrain(out, "dp", None, None)


def embed_inputs(params, cfg: ModelConfig, inputs: Dict[str, Any]):
    """Returns ([B,S,D] embeddings, loss-mask [B,S] or None)."""
    tok_emb = embed_tokens(params, cfg, inputs["tokens"])
    if cfg.frontend == "vision" and "patch_embeds" in inputs:
        pe = inputs["patch_embeds"].astype(cfg.jdtype)
        h = jax.nn.gelu((pe @ params["vis_proj"]["w1"]).astype(f32)).astype(
            cfg.jdtype)
        vis = h @ params["vis_proj"]["w2"]
        vis = constrain(vis, "dp", None, None)
        emb = jnp.concatenate([vis, tok_emb], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros(vis.shape[:2], bool), jnp.ones(tok_emb.shape[:2], bool)],
            axis=1)
        return emb, mask
    return tok_emb, None


def _mask_padded_vocab(logits, cfg: ModelConfig):
    """Padded vocab rows (sharding padding) never win: masked to -inf."""
    V, Vp = cfg.vocab_size, cfg.padded_vocab
    if V == Vp:
        return logits
    return jnp.where(jnp.arange(Vp) < V, logits, L.NEG_INF)


def chunked_cross_entropy(x, lm_head, labels, mask, chunk: int,
                          cfg: ModelConfig = None):
    """Per-chunk CE so [B,S,V] logits are never materialized whole."""
    B, S, D = x.shape
    V = lm_head.shape[-1]
    Sc = min(chunk, S)
    pad = (-S) % Sc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // Sc
    xc = x.reshape(B, nc, Sc, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, Sc).transpose(1, 0, 2)
    mc = mask.reshape(B, nc, Sc).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(xck, lck, mck):
        logits = jnp.einsum("bsd,dv->bsv", xck, lm_head,
                            preferred_element_type=f32)
        logits = constrain(logits, "dp", None, "vocab")
        if cfg is not None:
            logits = _mask_padded_vocab(logits, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lck[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * mck)

    def body(tot, inp):
        return tot + chunk_loss(*inp), None

    total, _ = jax.lax.scan(body, jnp.zeros((), f32), (xc, lc, mc))
    return total / jnp.maximum(jnp.sum(mask.astype(f32)), 1.0)


# ---------------------------------------------------------------------------
# KV-cache quantization (int8 values + per-token-per-head bf16 scales).
# A §Perf lever (EXPERIMENTS.md): halves decode KV-stream bytes; the paper's
# low-precision theme (FP4 weights) applied to the cache.


def _kv_quantize(row):
    """[..., dh] -> (int8 [..., dh], bf16 scale [...])."""
    amax = jnp.max(jnp.abs(row.astype(f32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(row.astype(f32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _kv_dequant(vals, scales, dtype):
    return (vals.astype(f32) * scales.astype(f32)[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Attention block (full sequence / chunk / decode)


def _head_map(cfg: ModelConfig):
    """Static q-head -> kv-head index map (padded q heads -> kv head 0,
    or h//g when kv heads are padded alongside q heads)."""
    import numpy as np
    H, Hp = cfg.num_heads, cfg.padded_heads
    g = H // cfg.num_kv_heads
    m = np.zeros((Hp,), np.int32)
    if cfg.padded_kv_heads * g == Hp:
        m = (np.arange(Hp) // g).astype(np.int32)
    else:
        m[:H] = np.arange(H) // g
    return jnp.asarray(m)


def _head_mask(cfg: ModelConfig):
    H, Hp = cfg.num_heads, cfg.padded_heads
    if H == Hp:
        return None
    return (jnp.arange(Hp) < H)


def _qkv(p, xn, cfg: ModelConfig, positions):
    B, S, _ = xn.shape
    q = jnp.einsum("bsd,dhk->bshk", xn, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xn, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xn, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "dp", None, "tp", None)
    k = constrain(k, "dp", None, None, None)
    v = constrain(v, "dp", None, None, None)
    return q, k, v


def _attn_out(p, o, cfg: ModelConfig):
    mask = _head_mask(cfg)
    if mask is not None:     # zero padded heads: exact semantics, zero grads
        o = o * mask[None, None, :, None].astype(o.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return constrain(out, "dp", None, None)


def attn_full(p, x, cfg: ModelConfig, *, pos_offset=0, impl="xla"):
    """Full causal self-attention over x. Returns (attn_out, (k, v))."""
    B, S, _ = x.shape
    positions = pos_offset + jnp.arange(S)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    hmap = _head_map(cfg)
    with jax.named_scope("attention"):
        kr = L.expand_kv(k, hmap)
        vr = L.expand_kv(v, hmap)
        if cfg.sliding_window:
            o = L.sliding_window_attention_xla(q, kr, vr, cfg.sliding_window)
        elif impl == "dense":
            o = L.dense_attention(q, kr, vr, causal=True)
        elif impl == "pallas":
            assert cfg.padded_heads == cfg.num_heads, \
                "pallas path: no padding"
            from repro.kernels.flash_attention.ops import flash_attention
            o = flash_attention(q, kr, vr, causal=True)
        else:
            o = L.causal_attention_xla(q, kr, vr)
    return _attn_out(p, o.astype(x.dtype), cfg), (k, v)


def _decode_attend(p, q, kd, vd, valid, cfg: ModelConfig):
    """Single-token attention core shared by the dense and paged decode
    paths: q [B,1,Hp,dh] against kd/vd [B,C,Hkvp,dh] with validity mask
    [B,C]. One implementation means the two layouts run the *same float
    ops* in the same order — masked columns contribute exact zeros after
    the NEG_INF mask, so dense and paged token streams stay bit-identical
    (asserted corpus-wide by tests/test_paged.py)."""
    with jax.named_scope("attention"):
        B = q.shape[0]
        scale = 1.0 / math.sqrt(cfg.dh)
        if cfg.grouped_decode and cfg.can_group_decode:
            # GQA without materializing the expanded KV: pack the q-head
            # group into the einsum (the decode-attention kernel's MXU
            # trick, in XLA)
            Hkvp = cfg.padded_kv_heads
            G = cfg.padded_heads // Hkvp
            qg = q[:, 0].reshape(B, Hkvp, G, cfg.dh)
            s = jnp.einsum("bhgd,bkhd->bhgk", qg, kd,
                           preferred_element_type=f32) * scale  # [B,Hkv,G,C]
            s = jnp.where(valid[:, None, None, :], s, L.NEG_INF)
            pr = jax.nn.softmax(s.astype(f32), axis=-1)
            o = jnp.einsum("bhgk,bkhd->bhgd", pr.astype(vd.dtype), vd,
                           preferred_element_type=f32)
            o = o.reshape(B, 1, cfg.padded_heads, cfg.dh)
        else:
            hmap = _head_map(cfg)
            kr = L.expand_kv(kd, hmap)
            vr = L.expand_kv(vd, hmap)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                           preferred_element_type=f32) * scale  # [B,H,1,C]
            s = jnp.where(valid[:, None, None, :], s, L.NEG_INF)
            pr = jax.nn.softmax(s.astype(f32), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(vr.dtype), vr,
                           preferred_element_type=f32)
    return o


def attn_decode(p, x1, cfg: ModelConfig, k_cache, v_cache, pos,
                scales=None):
    """x1: [B,1,D]; caches [B,C,Hkv,dh] (int8 + scales when kv_quant);
    pos: [B] per-slot positions (continuous batching)."""
    B = x1.shape[0]
    C = k_cache.shape[1]
    if cfg.sliding_window and C == cfg.sliding_window:
        slot = pos % C                                    # [B]
        kpos = pos[:, None] - jnp.mod(pos[:, None] - jnp.arange(C)[None], C)
    else:
        slot = jnp.minimum(pos, C - 1)
        kpos = jnp.broadcast_to(jnp.arange(C)[None], (B, C))
    q, k, v = _qkv(p, x1, cfg, pos[:, None])
    bidx = jnp.arange(B)
    if cfg.kv_quant:
        kq, ks_ = _kv_quantize(k[:, 0])
        vq, vs_ = _kv_quantize(v[:, 0])
        k_cache = k_cache.at[bidx, slot].set(kq)
        v_cache = v_cache.at[bidx, slot].set(vq)
        k_scale = scales["k_scale"].at[bidx, slot].set(ks_)
        v_scale = scales["v_scale"].at[bidx, slot].set(vs_)
        kd = _kv_dequant(k_cache, k_scale, x1.dtype)
        vd = _kv_dequant(v_cache, v_scale, x1.dtype)
        new_scales = {"k_scale": k_scale, "v_scale": v_scale}
    else:
        k_cache = k_cache.at[bidx, slot].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, slot].set(v[:, 0].astype(v_cache.dtype))
        kd, vd = k_cache, v_cache
        new_scales = {}
    valid = (kpos <= pos[:, None]) & (kpos >= 0)
    if cfg.sliding_window:
        valid &= kpos > pos[:, None] - cfg.sliding_window
    o = _decode_attend(p, q, kd, vd, valid, cfg)
    return (_attn_out(p, o.astype(x1.dtype), cfg),
            (k_cache, v_cache, new_scales))


def _chunk_attend(q, kr, vr, mask, cfg: ModelConfig, scale=None):
    """Mask-based chunk-attention core shared by the dense and paged
    prefill paths: q [B,Sq,H,dh] against *expanded* kr/vr [B,C,H,dh] with
    causal mask [Sq,C]. Shared for the same reason as ``_decode_attend``:
    identical float ops keep dense and paged prefill logits bit-equal."""
    with jax.named_scope("attention"):
        scale = 1.0 / math.sqrt(cfg.dh) if scale is None else scale
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                       preferred_element_type=f32) * scale
        s = jnp.where(mask[None, None], s, L.NEG_INF)
        pr = jax.nn.softmax(s.astype(f32), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(vr.dtype), vr,
                       preferred_element_type=f32)
    return o


def attn_chunk(p, x, cfg: ModelConfig, k_cache, v_cache, kv_offset):
    """Prefill chunk: x is tokens [off, off+Sq); cache holds [0, off)."""
    B, Sq, _ = x.shape
    positions = kv_offset + jnp.arange(Sq)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), kv_offset, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), kv_offset, axis=1)
    hmap = _head_map(cfg)
    kr = L.expand_kv(k_cache, hmap)
    vr = L.expand_kv(v_cache, hmap)
    # mask-based chunk attention (kv_offset is dynamic in serving)
    C = kr.shape[1]
    qpos = kv_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(C)[None, :]
    mask = kpos <= qpos
    if cfg.sliding_window:
        mask &= kpos > qpos - cfg.sliding_window
    o = _chunk_attend(q, kr, vr, mask, cfg)
    return _attn_out(p, o.astype(x.dtype), cfg), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3)
#
# A token's cache row is its latent [rms_norm(c_kv) | rope(k_pe) | 0]:
# kv_lora_rank + qk_rope_dim lanes, zero-padded to whole 128-lane tiles
# (``latent_lanes``), one row for every head. Prefill expands
# the latent rows through kv_b into per-head K and V and attends as MHA.
# Decode attends in the absorbed form over the rows themselves: each
# head's q_nope folds through its W_UK (kv_b's K columns) into the latent
# space, scores are q_lat . c_kv + q_pe . k_pe, and the softmax-weighted
# c_kv unfolds through its W_UV (kv_b's V columns). Rope is rotate-half
# over the rope lanes; the published checkpoints store those lanes
# interleaved, a fixed permutation of q_b's and kv_a's rope columns.


def _mla_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times YaRN's mscale squared."""
    return cfg.yarn_mscale() ** 2 / math.sqrt(cfg.qk_nope_dim
                                              + cfg.qk_rope_dim)


def _mla_rope(x, positions, cfg: ModelConfig):
    return L.apply_rope(x, positions, cfg.rope_theta, yarn=cfg.yarn)


def _mla_q(p, xn, cfg: ModelConfig, positions):
    """-> (q_nope [B,S,H,nope], roped q_pe [B,S,H,rope])."""
    if cfg.q_lora_rank:
        qa = jnp.einsum("bsd,dr->bsr", xn, p["wq_a"])
        qa = L.rms_norm(qa, p["q_a_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", qa, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", xn, p["wq"])
    n = cfg.qk_nope_dim
    return q[..., :n], _mla_rope(q[..., n:], positions, cfg)


def _mla_latent(p, xn, cfg: ModelConfig, positions):
    """Each token's cache row [B,S,latent_lanes]."""
    R = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", xn, p["wkv_a"])
    c = L.rms_norm(ckv[..., :R], p["kv_a_norm"], cfg.norm_eps)
    kpe = _mla_rope(ckv[..., None, R:], positions, cfg)[..., 0, :]
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_lanes - cfg.latent_dim,),
                    c.dtype)
    return jnp.concatenate([c, kpe.astype(c.dtype), pad], axis=-1)


def _mla_expand(p, lat, cfg: ModelConfig):
    """Latent rows [B,W,latent_lanes] -> per-head K [B,W,H,nope+rope] and
    V [B,W,H,v]: kv_b over c_kv, and k_pe shared by the heads."""
    R, n = cfg.kv_lora_rank, cfg.qk_nope_dim
    kv = jnp.einsum("bwr,rhk->bwhk", lat[..., :R], p["wkv_b"])
    kpe = jnp.broadcast_to(lat[:, :, None, R:cfg.latent_dim],
                           kv.shape[:3] + (cfg.qk_rope_dim,))
    return jnp.concatenate([kv[..., :n], kpe], axis=-1), kv[..., n:]


def mla_full(p, x, cfg: ModelConfig):
    """Full causal latent attention over x, expanded. Returns (attn_out,
    latent rows [B,S,latent_lanes])."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    qn, qr = _mla_q(p, x, cfg, positions)
    lat = _mla_latent(p, x, cfg, positions)
    k, v = _mla_expand(p, lat, cfg)
    q = jnp.concatenate([qn, qr], axis=-1)
    dv = v.shape[-1]
    with jax.named_scope("attention"):
        # the blocked XLA core takes one head size: V is zero-padded to
        # the q/k size and the padding sliced off the output
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q.shape[-1] - dv)))
        o = L.causal_attention_xla(q, k, vp, scale=_mla_scale(cfg))
    return _attn_out(p, o[..., :dv].astype(x.dtype), cfg), lat


def _mla_absorb(p, qn, qr, cfg: ModelConfig):
    """q_nope [B,H,nope], q_pe [B,H,rope] -> the query in the latent space
    [B,H,latent_lanes]: each head's q_nope through its W_UK."""
    w_uk = p["wkv_b"][..., :cfg.qk_nope_dim]                 # [R,H,nope]
    ql = jnp.einsum("bhk,rhk->bhr", qn, w_uk, preferred_element_type=f32)
    pad = jnp.zeros(qr.shape[:-1] + (cfg.latent_lanes - cfg.latent_dim,),
                    qn.dtype)
    return jnp.concatenate([ql.astype(qn.dtype), qr, pad], axis=-1)


def _mla_unabsorb(p, ol, cfg: ModelConfig):
    """Softmax-weighted c_kv [B,H,kv_lora] -> [B,1,H,v] through each
    head's W_UV."""
    w_uv = p["wkv_b"][..., cfg.qk_nope_dim:]                 # [R,H,v]
    o = jnp.einsum("bhr,rhk->bhk", ol.astype(w_uv.dtype), w_uv,
                   preferred_element_type=f32)
    return o[:, None]


def _mla_decode_attend(q, lat, valid, cfg: ModelConfig):
    """Absorbed single-token core shared by the dense and paged layouts:
    q [B,H,latent_lanes] against latent rows [B,W,latent_lanes] with
    validity mask [B,W]; each row is K whole and V in its first kv_lora
    lanes. Returns [B,H,kv_lora] f32."""
    with jax.named_scope("attention"):
        s = jnp.einsum("bhc,bwc->bhw", q, lat,
                       preferred_element_type=f32) * _mla_scale(cfg)
        s = jnp.where(valid[:, None, :], s, L.NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhw,bwr->bhr", pr.astype(lat.dtype),
                       lat[..., :cfg.kv_lora_rank],
                       preferred_element_type=f32)
    return o


def mla_decode(p, x1, cfg: ModelConfig, cache, pos):
    """x1: [B,1,D] (normed); cache [B,C,latent_lanes]; pos [B]. Writes
    this token's row at pos, then attends over rows [0, pos]."""
    B, C = cache.shape[:2]
    qn, qr = _mla_q(p, x1, cfg, pos[:, None])
    lat = _mla_latent(p, x1, cfg, pos[:, None])
    cache = cache.at[jnp.arange(B), jnp.minimum(pos, C - 1)].set(
        lat[:, 0].astype(cache.dtype))
    valid = jnp.arange(C)[None, :] <= pos[:, None]
    ol = _mla_decode_attend(_mla_absorb(p, qn[:, 0], qr[:, 0], cfg), cache,
                            valid, cfg)
    o = _mla_unabsorb(p, ol, cfg)
    return _attn_out(p, o.astype(x1.dtype), cfg), cache


# ---------------------------------------------------------------------------
# FFN dispatch


def _ffn(p, x, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        xn = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            y, aux = moe_lib.moe_ffn(xn, p["moe"], cfg.moe,
                                     combine_fp32=cfg.moe_combine_fp32,
                                     expert_tp=cfg.moe_expert_tp)
            return x + y, aux
        y = L.swiglu(xn, p["wi_gate"], p["wi_up"], p["wo_ffn"])
        return x + y, {}


def _zero_aux():
    return {"moe_aux_loss": jnp.zeros((), f32),
            "moe_z_loss": jnp.zeros((), f32),
            "moe_dropped": jnp.zeros((), f32)}


def _pad_aux(aux):
    """The three summed training metrics (a held-expert layer's routing
    hits are the decode step's, not training's)."""
    out = _zero_aux()
    out.update({k: v for k, v in aux.items() if k in out})
    return out


# ---------------------------------------------------------------------------
# Layer bodies (per family) for the three modes


def _layer_train(p, x, cfg: ModelConfig, impl: str):
    if cfg.block == "rwkv":
        B = x.shape[0]
        state = rwkv6.init_rwkv_state(cfg, B)
        x, _ = rwkv6.rwkv_block(p, x, state, cfg)
        return x, _zero_aux()
    xn = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if cfg.mla:
        attn_out, _ = mla_full(p, xn, cfg)
    else:
        attn_out, _ = attn_full(p, xn, cfg, impl=impl)
    if cfg.block == "hybrid":
        ssm_out, _ = ssm.ssm_apply(p["ssm"], xn, ssm.init_ssm_state(cfg, x.shape[0]), cfg)
        y = 0.5 * (L.rms_norm(attn_out, p["attn_out_norm"], cfg.norm_eps)
                   + L.rms_norm(ssm_out, p["ssm_out_norm"], cfg.norm_eps))
    else:
        y = attn_out
    x = x + y
    x, aux = _ffn(p, x, cfg)
    return x, _pad_aux(aux)


def _layer_prefill(p, x, cfg: ModelConfig, impl: str):
    """Like train, but also returns this layer's cache entry."""
    if cfg.block == "rwkv":
        B = x.shape[0]
        state = rwkv6.init_rwkv_state(cfg, B)
        x, new_state = rwkv6.rwkv_block(p, x, state, cfg)
        return x, new_state
    xn = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if cfg.mla:
        attn_out, lat = mla_full(p, xn, cfg)
        x, _ = _ffn(p, x + attn_out, cfg)
        return x, {"kv": lat}
    attn_out, (k, v) = attn_full(p, xn, cfg, impl=impl)
    entry = {}
    if cfg.block == "hybrid":
        B = x.shape[0]
        ssm_out, sstate = ssm.ssm_apply(p["ssm"], xn, ssm.init_ssm_state(cfg, B), cfg)
        y = 0.5 * (L.rms_norm(attn_out, p["attn_out_norm"], cfg.norm_eps)
                   + L.rms_norm(ssm_out, p["ssm_out_norm"], cfg.norm_eps))
        entry.update({"ssm_h": sstate["h"], "conv": sstate["conv"]})
        # ring buffer: keep the last W tokens in slot order (pos % W)
        W = cfg.sliding_window
        S = k.shape[1]
        if S >= W:
            last_k, last_v = k[:, S - W:], v[:, S - W:]
            roll = (S - W) % W
            entry["k"] = jnp.roll(last_k, shift=roll, axis=1)
            entry["v"] = jnp.roll(last_v, shift=roll, axis=1)
        else:
            padk = jnp.zeros((k.shape[0], W - S) + k.shape[2:], k.dtype)
            entry["k"] = jnp.concatenate([k, padk], axis=1)
            entry["v"] = jnp.concatenate([v, padk], axis=1)
    else:
        y = attn_out
        if cfg.kv_quant:
            entry["k"], entry["k_scale"] = _kv_quantize(k)
            entry["v"], entry["v_scale"] = _kv_quantize(v)
        else:
            entry["k"], entry["v"] = k, v
    x = x + y
    x, _ = _ffn(p, x, cfg)
    return x, entry


def _layer_decode(p, x1, cfg: ModelConfig, entry, pos):
    if cfg.block == "rwkv":
        x1, new_state = rwkv6.rwkv_block_step(p, x1, entry, cfg)
        return x1, new_state
    xn = L.rms_norm(x1, p["attn_norm"], cfg.norm_eps)
    if cfg.mla:
        attn_out, kv = mla_decode(p, xn, cfg, entry["kv"], pos)
        x1, _ = _ffn(p, x1 + attn_out, cfg)
        return x1, {"kv": kv}
    scales = ({"k_scale": entry["k_scale"], "v_scale": entry["v_scale"]}
              if cfg.kv_quant else None)
    attn_out, (k_c, v_c, new_scales) = attn_decode(
        p, xn, cfg, entry["k"], entry["v"], pos, scales=scales)
    new_entry = {"k": k_c, "v": v_c, **new_scales}
    if cfg.block == "hybrid":
        sstate = {"h": entry["ssm_h"], "conv": entry["conv"]}
        ssm_out, sstate2 = ssm.ssm_step(p["ssm"], xn, sstate, cfg)
        y = 0.5 * (L.rms_norm(attn_out, p["attn_out_norm"], cfg.norm_eps)
                   + L.rms_norm(ssm_out, p["ssm_out_norm"], cfg.norm_eps))
        new_entry.update({"ssm_h": sstate2["h"], "conv": sstate2["conv"]})
    else:
        y = attn_out
    x1 = x1 + y
    x1, _ = _ffn(p, x1, cfg)
    return x1, new_entry


# ---------------------------------------------------------------------------
# Layer stacks: the layer loop is one lax.scan per stack of identical layers


def layer_stacks(cfg: ModelConfig):
    """The stacks of identical layers, in order, as (params key, layer
    config, first layer, count). Leading dense layers of an MoE model
    (``first_k_dense``) are a stack of their own, ``dense_blocks``, run
    with the config's dense FFN; every other config is one stack,
    ``blocks``, and traces one scan as it always has."""
    k = cfg.dense_layers
    if not k:
        return (("blocks", cfg, 0, cfg.num_layers),)
    return (("dense_blocks", cfg.replace(moe=None), 0, k),
            ("blocks", cfg, k, cfg.num_layers - k))


def scan_layers(body, carry, params, cfg: ModelConfig, xs=None, *,
                remat: bool = False):
    """``lax.scan`` of ``body(layer_cfg, carry, (layer params, xs row))``
    over each stack in turn. ``xs`` has a leading layer axis (or is None).
    Returns (carry, [each stack's stacked outputs])."""
    stacks = layer_stacks(cfg)
    ys = []
    for name, lcfg, lo, n in stacks:
        fn = partial(body, lcfg)
        if remat:
            fn = jax.checkpoint(fn)
        rows = xs if len(stacks) == 1 else jax.tree.map(
            lambda a: a[lo:lo + n], xs)
        carry, y = jax.lax.scan(fn, carry, (params[name], rows))
        ys.append(y)
    return carry, ys


def _layers_concat(ys):
    """Per-stack outputs joined along the layer axis."""
    if len(ys) == 1:
        return ys[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys)


# ---------------------------------------------------------------------------
# Model-level entry points


def forward_hidden(params, cfg: ModelConfig, inputs, *, impl="xla"):
    """Training-mode forward to final hidden states. Returns (x, mask, aux)."""
    x, mask = embed_inputs(params, cfg, inputs)

    def body(lcfg, carry, inp):
        layer_p, _ = inp
        xc, aux_acc = carry
        xc, aux = _layer_train(layer_p, xc, lcfg, impl)
        return (xc, jax.tree.map(jnp.add, aux_acc, aux)), None

    (x, aux), _ = scan_layers(body, (x, _zero_aux()), params, cfg,
                              remat=cfg.remat)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    aux = {k: v / cfg.num_layers for k, v in aux.items()}
    return x, mask, aux


def train_loss(params, cfg: ModelConfig, batch, *, impl="xla"):
    """batch: {"tokens": [B,S], "labels": [B,S], (+"patch_embeds")}."""
    x, vis_mask, aux = forward_hidden(params, cfg, batch, impl=impl)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, bool)
    if vis_mask is not None:
        mask = mask & vis_mask
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ce = chunked_cross_entropy(x, head, labels, mask, cfg.logits_chunk,
                               cfg=cfg)
    loss = ce
    if cfg.moe is not None:
        loss = loss + 0.01 * aux["moe_aux_loss"] + 1e-3 * aux["moe_z_loss"]
    metrics = {"ce": ce, **aux}
    return loss, metrics


def init_cache(cfg: ModelConfig, batch: int, capacity: int):
    """Allocate an empty cache pytree (decoding starts at pos=0)."""
    Lr, B = cfg.num_layers, batch
    dt = cfg.jdtype
    if cfg.block == "rwkv":
        D, H = cfg.d_model, cfg.num_heads
        N = D // H
        return {
            "s": jnp.zeros((Lr, B, H, N, N), f32),
            "tm_x": jnp.zeros((Lr, B, D), dt),
            "cm_x": jnp.zeros((Lr, B, D), dt),
            "pos": jnp.zeros((B,), jnp.int32),
        }
    C = cfg.sliding_window if cfg.sliding_window else capacity
    if cfg.mla:
        return {"kv": jnp.zeros((Lr, B, C, cfg.latent_lanes), dt),
                "pos": jnp.zeros((B,), jnp.int32)}
    Hkvp = cfg.padded_kv_heads
    kv_dt = jnp.int8 if cfg.kv_quant else dt
    cache = {
        "k": jnp.zeros((Lr, B, C, Hkvp, cfg.dh), kv_dt),
        "v": jnp.zeros((Lr, B, C, Hkvp, cfg.dh), kv_dt),
        "pos": jnp.zeros((B,), jnp.int32),
    }
    if cfg.kv_quant:
        cache["k_scale"] = jnp.zeros((Lr, B, C, Hkvp), jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((Lr, B, C, Hkvp), jnp.bfloat16)
    if cfg.block == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        cache["ssm_h"] = jnp.zeros((Lr, B, di, cfg.ssm_state), f32)
        cache["conv"] = jnp.zeros((Lr, B, cfg.ssm_conv - 1, di), dt)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, capacity: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, capacity))


def _cache_keys(cfg: ModelConfig):
    if cfg.block == "rwkv":
        return ("s", "tm_x", "cm_x")
    if cfg.mla:
        return ("kv",)
    keys = ("k", "v") + (("k_scale", "v_scale") if cfg.kv_quant else ())
    if cfg.block == "hybrid":
        keys = keys + ("ssm_h", "conv")
    return keys


def prefill_full(params, cfg: ModelConfig, inputs, *, capacity: Optional[int] = None,
                 impl="xla"):
    """Single-shot prefill. Returns (logits [B,V], cache)."""
    emb, _mask = embed_inputs(params, cfg, inputs)
    B, S, _ = emb.shape
    capacity = capacity or S

    def body(lcfg, xc, inp):
        layer_p, _ = inp
        xc, entry = _layer_prefill(layer_p, xc, lcfg, impl)
        return xc, entry

    x, entries = scan_layers(body, emb, params, cfg, remat=cfg.remat)
    entries = _layers_concat(entries)
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x[:, -1], head,
                            preferred_element_type=f32)
        logits = _mask_padded_vocab(logits, cfg)
        logits = constrain(logits, "dp", "vocab")

    cache = dict(entries)
    if cfg.block == "attn":
        # grow cache to requested capacity
        if capacity > S:
            for key in _cache_keys(cfg):
                pad = jnp.zeros(cache[key].shape[:2] + (capacity - S,)
                                + cache[key].shape[3:], cache[key].dtype)
                cache[key] = jnp.concatenate([cache[key], pad], axis=2)
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """tokens: [B] int32. Returns (logits [B,V], updated cache)."""
    x = embed_tokens(params, cfg, tokens[:, None])
    pos = cache["pos"]
    entries = {k: cache[k] for k in _cache_keys(cfg)}

    def body(lcfg, x1, inp):
        layer_p, entry = inp
        x1, new_entry = _layer_decode(layer_p, x1, lcfg, entry, pos)
        return x1, new_entry

    x, new_entries = scan_layers(body, x, params, cfg, entries)
    new_entries = _layers_concat(new_entries)
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x[:, 0], head,
                            preferred_element_type=f32)
        logits = _mask_padded_vocab(logits, cfg)
        logits = constrain(logits, "dp", "vocab")
    new_cache = dict(new_entries)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def prefill_chunked(params, cfg: ModelConfig, inputs, chunk_size: int,
                    *, capacity: Optional[int] = None, impl="xla",
                    cache=None, start: int = 0):
    """Chunked (CPP-style) prefill: processes the prompt in ``chunk_size``
    pieces carrying cache/state between chunks. This is the executable analogue
    of the paper's context chunking (piggybacking) and CPP prefill.

    ``cache``/``start`` resume from an existing prefix (KV-cache reuse — the
    paper's §7 "KV cache reuse" future-work item): tokens[:, :start] must
    already be in the cache; only the suffix is processed.

    Only supported for attn-family here (rwkv/hybrid prefill is inherently
    chunked already via their scan). Returns (logits [B,V], cache).
    """
    assert cfg.block == "attn", "chunked prefill: attn family only"
    assert not cfg.kv_quant, "chunked prefill path keeps bf16 KV"
    assert not cfg.mla and not cfg.dense_layers, \
        "latent or mixed-layer models prefill in chunks on the paged layout"
    emb, _ = embed_inputs(params, cfg, inputs)
    B, S, D = emb.shape
    capacity = capacity or S
    assert (S - start) % chunk_size == 0 and start % max(chunk_size, 1) == 0         or start == 0 and S % chunk_size == 0
    nc = (S - start) // chunk_size
    if cache is None:
        cache = init_cache(cfg, B, capacity)

    def scan_layers(x, cache_kv, kv_offset):
        def body(carry, inp):
            xc, off = carry
            layer_p, (k_c, v_c) = inp
            xn = L.rms_norm(xc, layer_p["attn_norm"], cfg.norm_eps)
            attn_out, (k_c, v_c) = attn_chunk(layer_p, xn, cfg, k_c, v_c, off)
            xc = xc + attn_out
            xc, _ = _ffn(layer_p, xc, cfg)
            return (xc, off), (k_c, v_c)
        (x, _), kv = jax.lax.scan(body, (x, kv_offset),
                                  (params["blocks"], cache_kv))
        return x, kv

    logits = None
    kv = (cache["k"], cache["v"])
    x_last = None
    for i in range(nc):
        lo = start + i * chunk_size
        xc = emb[:, lo:lo + chunk_size]
        off = jnp.array(lo, jnp.int32)
        x_out, kv = scan_layers(xc, kv, off)
        x_last = x_out
    with jax.named_scope("head"):
        x = L.rms_norm(x_last, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x[:, -1], head,
                            preferred_element_type=f32)
        logits = _mask_padded_vocab(logits, cfg)
    cache = {"k": kv[0], "v": kv[1], "pos": jnp.full((B,), S, jnp.int32)}
    return logits, cache


# ---------------------------------------------------------------------------
# Paged KV cache (block pool + per-layer block tables)
#
# Layout: one pool of KV blocks shared by every layer and request,
#   pool = {"k", "v": [num_blocks, block_size, Hkvp*dh]}
# (latent attention: pool = {"kv": [num_blocks, block_size, latent_lanes]})
# with per-request *per-layer* block tables [L, nb] (int32 block ids).
# Host-side ownership/refcounts live in serving/blocks.py; everything here
# is the pure compute: decode gathers K/V through the table, prefill
# appends chunk KV into the request's own blocks. Block 0 is reserved as a
# scratch ("trash") block — padded table columns and inactive decode slots
# point at it so the jit'd step needs no liveness branches; the causal
# mask guarantees it is never read through a live position.


def supports_paged(cfg: ModelConfig) -> bool:
    """Paged serving covers the attention family, latent (MLA) included.
    Quantized KV keeps per-slot scale planes and sliding-window keeps a
    ring layout; both fall back to the dense per-slot cache (as do
    rwkv/hybrid recurrent states, which have no KV growth to page)."""
    return (cfg.block == "attn" and not cfg.kv_quant
            and not cfg.sliding_window)


def init_block_pool(cfg: ModelConfig, num_blocks: int, block_size: int):
    """Zero-filled block pool: {"k","v": [N, Bs, Hkvp*dh]} (a token's kv
    heads lie side by side in lanes), or for latent attention one
    {"kv": [N, Bs, latent_lanes]} (a token's latent row). Either way one
    block is Bs lane-dense rows, the layout the paged decode kernels read
    in place."""
    if cfg.mla:
        return {"kv": jnp.zeros((num_blocks, block_size, cfg.latent_lanes),
                                cfg.jdtype)}
    shape = (num_blocks, block_size, cfg.padded_kv_heads * cfg.dh)
    return {"k": jnp.zeros(shape, cfg.jdtype),
            "v": jnp.zeros(shape, cfg.jdtype)}


def gather_blocks(pool, ids):
    """ids [L, nb] -> free-floating block tensors, one [L, nb, Bs, lanes]
    per pool array — the paged KV-handoff payload (only the request's own
    blocks travel, never the whole pool)."""
    return {k: a[ids] for k, a in pool.items()}


def scatter_blocks(pool, ids, blocks):
    """Write handoff block tensors into the destination pool at ids [L, nb]
    (ids are distinct across layers: each layer owns its blocks)."""
    flat = ids.reshape(-1)
    return {k: a.at[flat].set(blocks[k].reshape((-1,) + a.shape[1:])
                              .astype(a.dtype))
            for k, a in pool.items()}


def attn_decode_paged(p, x1, cfg: ModelConfig, pool_k, pool_v, tbl, pos,
                      impl="xla"):
    """One decode token against the paged layout. x1: [B,1,D] (normed);
    pool_k/v: [N,Bs,Hkvp*dh]; tbl: [B,nb]; pos: [B]. Writes this token's
    K/V into the slot's current block, then attends over the slot's first
    pos+1 keys. Inactive slots must point at the trash block with pos=0
    (their write lands there; nothing reads it).

    ``impl="pallas"`` attends through ``kernels/decode_attention``'s paged
    kernel, which reads each slot's live blocks where they lie in the
    pool; ``"xla"`` gathers the pow2 window W = nb*Bs of every slot and
    runs the dense decode core (bit-equal logits with the dense cache)."""
    B = x1.shape[0]
    Bs = pool_k.shape[1]
    W = tbl.shape[1] * Bs
    q, k, v = _qkv(p, x1, cfg, pos[:, None])
    bidx = jnp.arange(B)
    wblk = tbl[bidx, pos // Bs]                               # [B]
    off = pos % Bs
    pool_k = pool_k.at[wblk, off].set(k[:, 0].reshape(B, -1)
                                      .astype(pool_k.dtype))
    pool_v = pool_v.at[wblk, off].set(v[:, 0].reshape(B, -1)
                                      .astype(pool_v.dtype))
    if impl == "pallas":
        assert cfg.padded_heads == cfg.num_heads, "pallas path: no padding"
        from repro.kernels.decode_attention.ops import decode_attention_paged
        with jax.named_scope("attention"):
            o = decode_attention_paged(q[:, 0], pool_k, pool_v, tbl, pos + 1)
        o = o[:, None]                                        # [B,1,H,dh]
    else:
        with jax.named_scope("kv_window"):
            kd = pool_k[tbl].reshape(B, W, cfg.padded_kv_heads, cfg.dh)
            vd = pool_v[tbl].reshape(B, W, cfg.padded_kv_heads, cfg.dh)
        valid = jnp.arange(W)[None, :] <= pos[:, None]
        o = _decode_attend(p, q, kd, vd, valid, cfg)
    return _attn_out(p, o.astype(x1.dtype), cfg), (pool_k, pool_v)


def mla_decode_paged(p, x1, cfg: ModelConfig, pool_kv, tbl, pos,
                     impl="xla"):
    """``attn_decode_paged`` for latent attention: writes this token's
    latent row into the slot's current block of pool_kv [N,Bs,latent_lanes]
    and attends in the absorbed form over the slot's first pos+1 rows.
    ``impl="pallas"`` runs the latent paged kernel, which copies each live
    block once and reads it as K (every lane) and V (the c_kv lanes);
    ``"xla"`` gathers the window and runs ``_mla_decode_attend``."""
    B, Bs = x1.shape[0], pool_kv.shape[1]
    W = tbl.shape[1] * Bs
    qn, qr = _mla_q(p, x1, cfg, pos[:, None])
    lat = _mla_latent(p, x1, cfg, pos[:, None])
    wblk = tbl[jnp.arange(B), pos // Bs]
    pool_kv = pool_kv.at[wblk, pos % Bs].set(lat[:, 0].astype(pool_kv.dtype))
    q = _mla_absorb(p, qn[:, 0], qr[:, 0], cfg)             # [B,H,lat]
    if impl == "pallas":
        from repro.kernels.decode_attention.ops import decode_attention_mla
        with jax.named_scope("attention"):
            ol = decode_attention_mla(q, pool_kv, tbl, pos + 1,
                                      scale=_mla_scale(cfg),
                                      value_lanes=cfg.kv_lora_rank)
    else:
        with jax.named_scope("kv_window"):
            ld = pool_kv[tbl].reshape(B, W, cfg.latent_lanes)
        ol = _mla_decode_attend(q, ld, jnp.arange(W)[None, :] <= pos[:, None],
                                cfg)
    o = _mla_unabsorb(p, ol, cfg)
    return _attn_out(p, o.astype(x1.dtype), cfg), pool_kv


def decode_step_paged(params, cfg: ModelConfig, pool, tables, pos, tokens,
                      impl="xla"):
    """Batched decode step on the paged layout. tables: [L,B,nb]; pos,
    tokens: [B]. Returns (logits [B,Vp], pool, out): ``out["pos"]`` is
    pos+1, and a held-expert MoE model adds ``out["hits"]``, [MoE layers,
    B, held experts] bool: which held experts each slot's token was routed
    to. The layer scan carries the pool, mirroring ``decode_step``'s
    cache carry — per layer it scatters B rows and gathers B*W rows
    instead of touching the whole dense [B,C] cache plane."""
    x = embed_tokens(params, cfg, tokens[:, None])

    def body(lcfg, carry, inp):
        x1, pool = carry
        layer_p, tbl = inp
        xn = L.rms_norm(x1, layer_p["attn_norm"], lcfg.norm_eps)
        if lcfg.mla:
            attn_out, kv = mla_decode_paged(layer_p, xn, lcfg, pool["kv"],
                                            tbl, pos, impl=impl)
            pool = {"kv": kv}
        else:
            attn_out, (pk, pv) = attn_decode_paged(
                layer_p, xn, lcfg, pool["k"], pool["v"], tbl, pos, impl=impl)
            pool = {"k": pk, "v": pv}
        x1 = x1 + attn_out
        x1, aux = _ffn(layer_p, x1, lcfg)
        return (x1, pool), aux.get("held_hits")

    (x, pool), hits = scan_layers(body, (x, pool), params, cfg, tables)
    hits = [h for h in hits if h is not None]
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x[:, 0], head,
                            preferred_element_type=f32)
        logits = _mask_padded_vocab(logits, cfg)
        logits = constrain(logits, "dp", "vocab")
    out = {"pos": pos + 1}
    if hits:
        out["hits"] = hits[0]
    return logits, pool, out


def prefill_chunked_paged(params, cfg: ModelConfig, inputs, chunk_size: int,
                          pool, tables, *, start: int = 0, impl="xla"):
    """Chunked prefill that appends straight into the request's blocks
    (no dense B=1 cache is ever built). B=1; tables: [L, nb] covering at
    least the prompt; chunk_size % block_size == 0 so every chunk lands on
    block boundaries. ``start`` resumes after a prefix-cache hit (those
    blocks already hold the prefix KV). Returns (logits [B,Vp], pool).

    ``impl="pallas"`` runs each chunk through the flash-attention kernel
    on the gathered (contiguous) context with ``q_offset`` — the
    chunked-prefill wiring for ``kernels/flash_attention``; ``"xla"``
    uses the same mask-based core as the dense path (bit-equal logits).
    Latent attention writes each chunk's latent rows, expands the
    gathered window through kv_b, and attends in XLA.
    """
    assert cfg.block == "attn" and not cfg.kv_quant
    emb, _ = embed_inputs(params, cfg, inputs)
    B, S, _ = emb.shape
    assert B == 1, "paged prefill is per-request (B=1)"
    Bs = next(iter(pool.values())).shape[1]
    Hkvp, dh = cfg.padded_kv_heads, cfg.dh
    nb = tables.shape[1]
    W = nb * Bs
    assert chunk_size % Bs == 0, "chunks must be block-aligned"
    assert (S - start) % chunk_size == 0 and start % chunk_size == 0
    assert S <= W, f"prompt {S} exceeds table window {W}"
    cb = chunk_size // Bs

    def chunk_layers(x, pool, lo):
        # lo is a python int: block offsets below are static slices
        def body(lcfg, carry, inp):
            xc, pool = carry
            layer_p, tbl = inp                            # tbl: [nb]
            xn = L.rms_norm(xc, layer_p["attn_norm"], lcfg.norm_eps)
            positions = lo + jnp.arange(chunk_size)[None, :]
            wids = tbl[lo // Bs:lo // Bs + cb]
            qpos = lo + jnp.arange(chunk_size)[:, None]
            if lcfg.mla:
                qn, qr = _mla_q(layer_p, xn, lcfg, positions)
                lat = _mla_latent(layer_p, xn, lcfg, positions)
                pkv = pool["kv"].at[wids].set(
                    lat[0].reshape(cb, Bs, -1).astype(pool["kv"].dtype))
                pool = {"kv": pkv}
                with jax.named_scope("kv_window"):
                    ld = pkv[tbl].reshape(1, W, lcfg.latent_lanes)
                k, v = _mla_expand(layer_p, ld, lcfg)
                o = _chunk_attend(jnp.concatenate([qn, qr], axis=-1), k, v,
                                  jnp.arange(W)[None, :] <= qpos, lcfg,
                                  scale=_mla_scale(lcfg))
                xc = xc + _attn_out(layer_p, o.astype(xc.dtype), lcfg)
                xc, _ = _ffn(layer_p, xc, lcfg)
                return (xc, pool), None
            pk, pv = pool["k"], pool["v"]
            q, k, v = _qkv(layer_p, xn, lcfg, positions)
            pk = pk.at[wids].set(
                k[0].reshape(cb, Bs, Hkvp * dh).astype(pk.dtype))
            pv = pv.at[wids].set(
                v[0].reshape(cb, Bs, Hkvp * dh).astype(pv.dtype))
            with jax.named_scope("kv_window"):
                kd = pk[tbl].reshape(1, W, Hkvp, dh)
                vd = pv[tbl].reshape(1, W, Hkvp, dh)
            ctx = lo + chunk_size
            if impl == "pallas":
                assert lcfg.padded_heads == lcfg.num_heads, \
                    "pallas path: no padding"
                from repro.kernels.flash_attention.ops import flash_attention
                o = flash_attention(
                    q, kd[:, :ctx], vd[:, :ctx], causal=True, q_offset=lo,
                    block_q=chunk_size, block_kv=chunk_size)
            else:
                hmap = _head_map(lcfg)
                kr = L.expand_kv(kd, hmap)
                vr = L.expand_kv(vd, hmap)
                mask = jnp.arange(W)[None, :] <= qpos
                o = _chunk_attend(q, kr, vr, mask, lcfg)
            xc = xc + _attn_out(layer_p, o.astype(xc.dtype), lcfg)
            xc, _ = _ffn(layer_p, xc, lcfg)
            return (xc, {"k": pk, "v": pv}), None

        (x, pool), _ = scan_layers(body, (x, pool), params, cfg, tables)
        return x, pool

    x_last = None
    for lo in range(start, S, chunk_size):
        x_last, pool = chunk_layers(emb[:, lo:lo + chunk_size], pool, lo)
    with jax.named_scope("head"):
        x = L.rms_norm(x_last, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x[:, -1], head,
                            preferred_element_type=f32)
        logits = _mask_padded_vocab(logits, cfg)
    return logits, pool


def verify_chunk(params, cfg: ModelConfig, cache, tokens, start):
    """Score `tokens` [B,k] at positions [start, start+k) against the cache
    (per-position logits — the speculative-decoding verify pass). Writes the
    tokens' KV into the cache; rejected suffixes are simply overwritten by
    the next call (causally masked meanwhile). Returns (logits [B,k,Vp],
    cache). attn-family only.
    """
    assert cfg.block == "attn" and not cfg.kv_quant
    assert not cfg.mla and not cfg.dense_layers
    emb, _ = embed_inputs(params, cfg, {"tokens": tokens})
    kv = (cache["k"], cache["v"])
    off = jnp.asarray(start, jnp.int32)

    def body(carry, inp):
        xc, o = carry
        layer_p, (k_c, v_c) = inp
        xn = L.rms_norm(xc, layer_p["attn_norm"], cfg.norm_eps)
        attn_out, (k_c, v_c) = attn_chunk(layer_p, xn, cfg, k_c, v_c, o)
        xc = xc + attn_out
        xc, _ = _ffn(layer_p, xc, cfg)
        return (xc, o), (k_c, v_c)

    (x, _), kv = jax.lax.scan(body, (emb, off), (params["blocks"], kv))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head, preferred_element_type=f32)
    logits = _mask_padded_vocab(logits, cfg)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = kv
    new_cache["pos"] = jnp.full_like(cache["pos"], start + tokens.shape[1])
    return logits, new_cache
