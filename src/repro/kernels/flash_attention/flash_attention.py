"""Causal GQA flash attention for TPU (prefill / chunked-prefill).

TPU-native design notes (vs the CUDA FlashAttention algorithm):
  - Tiling is chosen for VMEM (not shared memory): q tile [Bq, dh], k/v tiles
    [Bk, dh] with Bq=Bk=256 default -> ~(2*256*128*2B)*2 + accum 256*128*4B
    ≈ 0.6 MB per (q,kv) tile set, comfortably inside ~16 MB VMEM with
    double-buffered pipelines.
  - Heads are folded into the lane axis: q/k/v arrive as [B, S, H*dh]
    (a free reshape of [B, S, H, dh]), so every block tail is (rows, dh)
    and meets Mosaic's (8, 128) tiling rule at dh=128. Softmax statistics
    live in [Bq, 1] column scratch.
  - GQA is handled in the *index map*: query head h reads the lane block
    of KV head h // q_group, so KV tiles are never materialized per-q-head
    in HBM.
  - The KV grid axis is sequential ("arbitrary"); the online-softmax partial
    state (acc, m, l) persists in VMEM scratch across KV steps — the TPU
    analogue of FlashAttention's per-CTA registers.
  - Fully-masked tiles (KV block entirely in the causal future) are skipped
    with pl.when: no MXU work, no VMEM traffic beyond the prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, q_offset: int, block_q: int, block_kv: int,
                  kv_blocks: int, causal: bool):
    i = pl.program_id(2)           # q block index
    j = pl.program_id(3)           # kv block index

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block j is live iff its first row index <= q block's last row
    q_last = q_offset + (i + 1) * block_q - 1
    live = (j * block_kv <= q_last) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                          # [Bq, dh]
        k = k_ref[0]                                          # [Bk, dh]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [Bq, Bk]
        if causal:
            rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]                                   # [Bq, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > 0.5 * NEG_INF, p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, scale: float, causal: bool = True,
                           q_offset: int = 0, block_q: int = 256,
                           block_kv: int = 256, interpret: bool = False):
    """q: [B, Sq, H, dh]; k, v: [B, Skv, Hkv, dh]; H % Hkv == 0.

    Heads are folded into lanes before the call ([B, S, H*dh], a free
    reshape) so the blocks are (rows, dh) slices of a 2-D tail."""
    B, Sq, H, dh = q.shape
    _, Skv, Hkv, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    q_blocks = Sq // block_q
    kv_blocks = Skv // block_kv
    grid = (B, H, q_blocks, kv_blocks)

    kernel = functools.partial(
        _flash_kernel, scale=scale, q_offset=q_offset, block_q=block_q,
        block_kv=block_kv, kv_blocks=kv_blocks, causal=causal)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, block_kv, dh),
                         lambda b, h, i, j, g=group: (b, j, h // g)),
            pl.BlockSpec((1, block_kv, dh),
                         lambda b, h, i, j, g=group: (b, j, h // g)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.reshape(B, Sq, H * dh), k.reshape(B, Skv, Hkv * dh),
      v.reshape(B, Skv, Hkv * dh)).reshape(B, Sq, H, dh)
