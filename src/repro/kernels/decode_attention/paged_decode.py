"""Paged split-KV flash decoding for TPU (single-token GQA decode).

Same MXU packing and online-softmax split algebra as
``decode_attention.py``, but K/V live in a *block pool*
[num_blocks, block_size, Hkv, dh] indexed through per-sequence block
tables instead of a dense [B, Smax, ...] cache — the serving engine's
paged layout streams straight into the kernel with no gather/copy pass.

The block table rides in as a *scalar-prefetch* operand
(``PrefetchScalarGridSpec``): the BlockSpec index map for K/V reads
``tables[b, j]`` to pick which pool block the pipeline DMAs next, so the
indirection costs nothing in the kernel body — grid step (b, h, j)
simply sees "its" block in VMEM. Each table entry is one split of the
kv axis; splits are parallel grid steps exactly like the dense kernel's
``Smax/block_kv`` splits, and the tiny cross-split reduction happens in
the jit'd wrapper (ops.py).

Dead splits (whole block past the sequence length — pow2-padded table
columns point at the reserved trash block) skip all compute with
``pl.when`` and emit (0, -inf, 0) partials that the merge ignores.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.decode_attention import (attend_block,
                                                            partial_specs)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                  l_ref, *, scale: float, block_size: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    attend_block(q_ref[0, 0], k_ref[0], v_ref[0], j * block_size,
                 len_ref[b], o_ref.at[0, 0, 0], m_ref.at[0, 0, 0],
                 l_ref.at[0, 0, 0], scale=scale)


def paged_decode_attention_kernel(q, pool_k, pool_v, tables, lengths, *,
                                  scale: float, interpret: bool = False):
    """q: [B, Hkv, G, dh]; pools: [N, Bs, Hkv, dh]; tables: [B, nb] int32;
    lengths: [B] int32 (valid positions within the gathered window).

    Returns partials (o [B,Hkv,nb,G,dh] f32, m, l [B,Hkv,nb,G]) — one
    split per table entry, merged by the caller. The pool is viewed as
    [N, Bs, Hkv*dh] (a free reshape): a K/V block is the (Bs, dh) lane
    slice of one kv head in one pool block.
    """
    B, Hkv, G, dh = q.shape
    N, block_size = pool_k.shape[:2]
    nb = tables.shape[1]

    kernel = functools.partial(_paged_kernel, scale=scale,
                               block_size=block_size)
    out_specs, out_shape = partial_specs(
        B, Hkv, nb, G, dh, lambda b, h, j, tbl, lens: (b, h, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh),
                         lambda b, h, j, tbl, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, block_size, dh),
                         lambda b, h, j, tbl, lens: (tbl[b, j], 0, h)),
            pl.BlockSpec((1, block_size, dh),
                         lambda b, h, j, tbl, lens: (tbl[b, j], 0, h)),
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
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      pool_k.reshape(N, block_size, Hkv * dh),
      pool_v.reshape(N, block_size, Hkv * dh))
    return o, m[..., 0], l[..., 0]
