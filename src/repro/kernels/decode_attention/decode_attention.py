"""Split-KV flash decoding for TPU (single-token GQA decode).

TPU-native rethinking of FlashDecoding (GPU: one CTA per KV split, shuffle
reduction). Here:
  - The whole *q-head group* of a KV head (G = H/Hkv rows) is packed into the
    MXU matmul M dimension, so decode matmuls are [G, dh] x [dh, Bk] instead
    of G separate vector-matrix products — the TPU analogue of the
    tensor-core packing trick (keeps the 128x128 MXU from running at 1/G
    utilization).
  - The KV sequence axis is split across a parallel grid dimension; each
    split emits unnormalized partials (o, m, l) and the tiny cross-split
    online-softmax reduction happens in the jit'd wrapper (ops.py) — on real
    hardware the splits execute concurrently across TensorCores.
  - Per-sequence valid lengths (continuous batching!) mask the tail split via
    iota comparison; fully-dead splits skip all compute with pl.when.
  - Heads are folded into the lane axis, so every block tail is (rows, dh)
    and the kernel lowers for the TPU at dh=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def attend_block(q, k, v, start, length, o_ref, m_ref, l_ref, *,
                 scale: float):
    """One kv split's unnormalized partials: q [G, dh] against k/v
    [Bk, dh] whose first row is sequence position ``start``. Writes
    o [G, dh] and the row statistics m, l [G, 1]; a split wholly past
    ``length`` writes (0, NEG_INF, 0), which the merge ignores."""
    G, block = q.shape[0], k.shape[0]

    @pl.when(start < length)
    def _compute():
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [G, Bk]
        cols = start + jax.lax.broadcasted_iota(jnp.int32, (G, block), 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)                # [G, 1]
        p = jnp.exp(s - m)
        p = jnp.where(m > 0.5 * NEG_INF, p, 0.0)
        o_ref[...] = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m
        l_ref[...] = jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(start >= length)
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   scale: float, block_kv: int):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    attend_block(q_ref[0, 0], k_ref[0], v_ref[0], s_idx * block_kv,
                 len_ref[b], o_ref.at[0, 0, 0], m_ref.at[0, 0, 0],
                 l_ref.at[0, 0, 0], scale=scale)


def partial_specs(B, Hkv, splits, G, dh, index_map):
    """Out specs and shapes of the (o, m, l) partials. m and l carry a
    trailing unit axis so every block tail, (G, dh) or (G, 1), equals
    the array's own and meets the TPU lowering's tiling rule."""
    specs = [pl.BlockSpec((1, 1, 1, G, dh), index_map),
             pl.BlockSpec((1, 1, 1, G, 1), index_map),
             pl.BlockSpec((1, 1, 1, G, 1), index_map)]
    shapes = [jax.ShapeDtypeStruct((B, Hkv, splits, G, dh), jnp.float32),
              jax.ShapeDtypeStruct((B, Hkv, splits, G, 1), jnp.float32),
              jax.ShapeDtypeStruct((B, Hkv, splits, G, 1), jnp.float32)]
    return specs, shapes


def decode_attention_kernel(q, k_cache, v_cache, lengths, *, scale: float,
                            block_kv: int = 512, interpret: bool = False):
    """q: [B, Hkv, G, dh]; caches: [B, Smax, Hkv, dh]; lengths: [B] int32.

    Returns partials (o [B,Hkv,S_splits,G,dh] f32, m, l [B,Hkv,S_splits,G]).
    Heads are folded into lanes ([B, Smax, Hkv*dh], a free reshape), so a
    K/V block is the (block_kv, dh) lane slice of one kv head; lengths
    are scalar-prefetched into SMEM whole.
    """
    B, Hkv, G, dh = q.shape
    Smax = k_cache.shape[1]
    block_kv = min(block_kv, Smax)
    assert Smax % block_kv == 0
    splits = Smax // block_kv

    kernel = functools.partial(_decode_kernel, scale=scale, block_kv=block_kv)
    out_specs, out_shape = partial_specs(
        B, Hkv, splits, G, dh, lambda b, h, s, lens: (b, h, s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, splits),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, block_kv, dh), lambda b, h, s, lens: (b, s, h)),
            pl.BlockSpec((1, block_kv, dh), lambda b, h, s, lens: (b, s, h)),
        ],
        out_specs=out_specs,
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(lengths, q, k_cache.reshape(B, Smax, Hkv * dh),
      v_cache.reshape(B, Smax, Hkv * dh))
    return o, m[..., 0], l[..., 0]
