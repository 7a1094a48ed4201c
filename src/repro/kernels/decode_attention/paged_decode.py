"""Paged decode attention for TPU: each slot reads its own live KV blocks
where they lie in the pool (single-token decode).

Both kernels read one pool ``[N, Bs, lanes]``: block ``n`` holds ``Bs``
token rows of lane-dense ``lanes``, and the kernels take the pool in the
layout XLA keeps it in (no relayout, no gather). Block tables ride in as
scalar-prefetch operands.

One grid step per slot, in order. Inside it a loop runs over the slot's
*live* chunks only, ``P`` pool blocks (``P*Bs`` keys) each: the chunk's
live blocks are copied HBM -> VMEM by one DMA per block, double-buffered,
so the next chunk (or the next slot's first) is in flight while this one
is computed. Blocks and chunks past the slot's length are never fetched,
so a slot costs its live blocks, not the table's width. The two kernels
share that loop (``_slot_chunks``) and differ in what a row holds:

``paged_decode_attention_kernel`` (GQA): a token's row is its K heads in
the first ``Hkv*dh`` lanes, its V heads in the next ``Hkv*dh``, so one
copy brings a block's K and V. Every kv head is a lane-aligned ``dh``
slice of either half; its q-head group ``G`` is packed into the MXU M
dimension. The online-softmax state (m, l, acc per kv head) is carried
on chip across the chunks, and the normalized ``[Hkv, G, dh]`` output is
written once per slot.

``paged_latent_decode_kernel`` (MLA, absorbed form): one latent row
``[R + rope]`` per token, shared by every head, serves as K (all of its
lanes) and as V (its first R lanes, the c_kv part). All H query heads,
already folded into the latent space, go against that one copy in the
MXU's M dimension: scores ``[H, chunk]``, output ``[H, R]`` per slot.

Numerics follow the XLA decode path: bf16 K/V and q, scores and softmax
in f32, ``p`` cast to the KV dtype before P.V with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_KEYS = 256              # keys per chunk, at most
CHUNK_BYTES = 512 << 10       # bytes of a chunk buffer (GQA: K and V), at most
# the latent kernel's chunks: 25 blocks (200 keys) at DeepSeek-V3's widths
LATENT_CHUNK_BYTES = 256 << 10


def chunk_blocks(block_size: int, lanes: int, itemsize: int, nb: int,
                 max_bytes: int = CHUNK_BYTES) -> int:
    """Pool blocks per chunk: up to CHUNK_KEYS keys and ``max_bytes`` of
    pool rows ``lanes`` wide, never more than the table holds."""
    p = min(CHUNK_KEYS // block_size,
            max_bytes // (block_size * lanes * itemsize))
    return max(1, min(p, nb))


def _slot_chunks(tbl_ref, len_ref, hbm, buf, sems, slot_ref, *, nb: int,
                 attend, state):
    """The loop of grid step ``b``: fold ``attend(rows, key0, length,
    state)`` over slot ``b``'s live chunks, each ``rows`` [P*Bs, lanes]
    the chunk's blocks and ``key0`` the position of its first key, while
    the DMAs of the next chunk, or of the next slot's first, run. Returns
    the final state."""
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, P, Bs, lanes = buf.shape
    chunk = P * Bs

    def live_blocks(s):
        # a slot of length 0 still takes one chunk, all of it masked
        return jnp.maximum(pl.cdiv(len_ref[s], Bs), 1)

    def copy(s, c, i, slot):
        return pltpu.make_async_copy(hbm.at[tbl_ref[s * nb + c * P + i]],
                                     buf.at[slot, i], sems.at[slot])

    def n_live(s, c):
        return jnp.minimum(live_blocks(s) - c * P, P)

    def start(s, c, slot):
        jax.lax.fori_loop(0, n_live(s, c),
                          lambda i, _: copy(s, c, i, slot).start(), None)

    def wait(s, c, slot):
        n = n_live(s, c)

        @pl.when(n == P)
        def _full():
            # the P copies signal one semaphore: one wait for the whole
            # buffer's bytes covers them
            pltpu.make_async_copy(hbm.at[pl.ds(0, P)], buf.at[slot],
                                  sems.at[slot]).wait()

        @pl.when(n < P)
        def _part():
            jax.lax.fori_loop(0, n, lambda i, _: copy(s, c, i, slot).wait(),
                              None)

    @pl.when(b == 0)
    def _first():
        # rows past a slot's live blocks keep what an earlier chunk left:
        # zero the buffers once, so those rows are finite and p = 0 there
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0
        start(0, 0, 0)

    length = len_ref[b]
    nch = pl.cdiv(live_blocks(b), P)

    def body(c, state):
        cur = slot_ref[0]
        nxt = 1 - cur
        # prefetch what comes next: this slot's next chunk, else the next
        # slot's first
        more = c + 1 < nch
        ns = jnp.where(more, b, b + 1)
        nc = jnp.where(more, c + 1, 0)

        @pl.when(ns < nslots)
        def _prefetch():
            start(ns, nc, nxt)

        wait(b, c, cur)
        slot_ref[0] = nxt
        return attend(buf[cur].reshape(chunk, lanes), c * chunk, length,
                      state)

    return jax.lax.fori_loop(0, nch, body, state)


def _scratch(P, Bs, lanes, dtype):
    """Both buffers of the chunk copies, a DMA semaphore each, and the
    index of the buffer the next chunk lands in."""
    return [pltpu.VMEM((2, P, Bs, lanes), dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32)]


def _paged_kernel(tbl_ref, len_ref, q_ref, kv_hbm, o_ref, buf, sems,
                  slot_ref, *, scale: float, nb: int):
    Hkv, G, dh = q_ref.shape[1:]
    half = Hkv * dh                                 # V's lanes start here
    q = q_ref[0]                                             # [Hkv, G, dh]

    def attend(kv, key0, length, state):
        cols = key0 + jax.lax.broadcasted_iota(jnp.int32,
                                               (G, kv.shape[0]), 1)
        live = cols < length
        new = []
        for h, (m_prev, l_prev, acc) in enumerate(zip(*state)):
            kh = kv[:, h * dh:(h + 1) * dh]
            vh = kv[:, half + h * dh:half + (h + 1) * dh]
            s = jax.lax.dot_general(
                q[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [G, chunk]
            s = jnp.where(live, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            new.append((m_new,
                        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                        alpha * acc + jax.lax.dot_general(
                            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)))  # [G, dh]
        return tuple(zip(*new))

    # the online-softmax state of each kv head: m, l [G, 1], acc [G, dh]
    state = ((jnp.full((G, 1), NEG_INF, jnp.float32),) * Hkv,
             (jnp.zeros((G, 1), jnp.float32),) * Hkv,
             (jnp.zeros((G, dh), jnp.float32),) * Hkv)
    _, ls, accs = _slot_chunks(tbl_ref, len_ref, kv_hbm, buf, sems, slot_ref,
                               nb=nb, attend=attend, state=state)
    for h in range(Hkv):
        o_ref[0, h] = (accs[h] / jnp.maximum(ls[h], 1e-30)).astype(
            o_ref.dtype)


def paged_decode_attention_kernel(q, pool, tables, lengths, *, scale: float,
                                  interpret: bool = False):
    """q: [B, Hkv, G, dh]; pool: [N, Bs, 2*Hkv*dh], each row a token's K
    heads then its V heads; tables: [B, nb] int32 block ids in sequence
    order; lengths: [B] int32 keys to attend, each at most nb*Bs. Returns
    the attention output [B, Hkv, G, dh]."""
    B, Hkv, G, dh = q.shape
    _, Bs, lanes = pool.shape
    assert lanes == 2 * Hkv * dh, "pool rows must be K and V: 2*Hkv*dh"
    nb = tables.shape[1]
    P = chunk_blocks(Bs, lanes, pool.dtype.itemsize, nb)
    kernel = functools.partial(_paged_kernel, scale=scale, nb=nb)
    qspec = pl.BlockSpec((1, Hkv, G, dh), lambda b, tbl, lens: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qspec,
        scratch_shapes=_scratch(P, Bs, lanes, pool.dtype),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        # the DMAs of one slot's step prefetch the next slot's first
        # chunk, so the slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32), q,
      pool)


def _latent_kernel(tbl_ref, len_ref, q_ref, kv_hbm, o_ref, buf, sems,
                   slot_ref, *, scale: float, nb: int, value_lanes: int):
    H = q_ref.shape[1]
    q = q_ref[0]                                             # [H, lanes]

    def attend(kv, key0, length, state):
        m_prev, l_prev, acc = state
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = key0 + jax.lax.broadcasted_iota(jnp.int32,
                                               (H, kv.shape[0]), 1)
        live = cols < length
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        pv = jax.lax.dot_general(p.astype(kv.dtype), kv[:, :value_lanes],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return (m_new, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + pv)

    state = (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, value_lanes), jnp.float32))
    _, l, acc = _slot_chunks(tbl_ref, len_ref, kv_hbm, buf, sems, slot_ref,
                             nb=nb, attend=attend, state=state)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_latent_decode_kernel(q, pool, tables, lengths, *, scale: float,
                               value_lanes: int, interpret: bool = False):
    """q: [B, H, lanes] (queries in the latent space); pool: [N, Bs,
    lanes] latent rows; tables: [B, nb] int32 block ids in sequence
    order; lengths: [B] int32 rows to attend, each at most nb*Bs. Keys are
    whole rows, values their first ``value_lanes`` lanes. Returns
    [B, H, value_lanes]."""
    B, H, lanes = q.shape
    _, Bs, pool_lanes = pool.shape
    assert pool_lanes == lanes, "q and pool rows must have the same lanes"
    nb = tables.shape[1]
    P = chunk_blocks(Bs, lanes, pool.dtype.itemsize, nb,
                     max_bytes=LATENT_CHUNK_BYTES)
    kernel = functools.partial(_latent_kernel, scale=scale, nb=nb,
                               value_lanes=value_lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, lanes), lambda b, tbl, lens: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_lanes),
                               lambda b, tbl, lens: (b, 0, 0)),
        scratch_shapes=_scratch(P, Bs, lanes, pool.dtype),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_lanes), q.dtype),
        # one slot's step prefetches the next slot's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32), q,
      pool)
