"""Paged decode attention for TPU: each slot reads its own live KV blocks
where they lie in the pool (single-token GQA decode).

The pool is ``[N, Bs, Hkv*dh]``: block ``n`` holds ``Bs`` tokens, each
token's kv heads side by side in lanes, so one block is ``Bs`` rows of
lane-dense ``Hkv*dh`` and the kernel takes the pool in the layout XLA
keeps it in (no relayout, no gather). Block tables ride in as
scalar-prefetch operands.

One grid step per slot, in order. Inside it a loop runs over the slot's
*live* chunks only, ``P`` pool blocks (``P*Bs`` keys) each: the chunk's
live blocks are copied HBM -> VMEM by one DMA per block, double-buffered,
so the next chunk (or the next slot's first) is in flight while this one
is computed. Blocks and chunks past the slot's length are never fetched,
so a slot costs its live blocks, not the table's width.
Every kv head of the chunk is a lane-aligned ``dh`` slice; its q-head
group ``G`` is packed into the MXU M dimension. The online-softmax state
(m, l, acc per kv head) is carried on chip across the chunks, and the
normalized ``[Hkv, G, dh]`` output is written once per slot.

Numerics follow the XLA decode path: bf16 K/V and q, scores and softmax
in f32, ``p`` cast to the KV dtype before P.V with f32 accumulation.

``paged_latent_decode_kernel`` is the same loop for latent attention
(MLA, absorbed form). The pool is ``[N, Bs, R + rope]``: one latent row
per token, shared by every head. Each live block is copied once, by one
DMA, and serves as K (all of its lanes) and as V (its first R lanes, the
c_kv part). All H query heads, already folded into the latent space, go
against that one copy in the MXU's M dimension: scores ``[H, chunk]``,
output ``[H, R]`` per slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_KEYS = 256              # keys per chunk, at most
CHUNK_BYTES = 256 << 10       # bytes of K per chunk buffer, at most


def chunk_blocks(block_size: int, lanes: int, itemsize: int,
                 nb: int) -> int:
    """Pool blocks per chunk: up to CHUNK_KEYS keys and CHUNK_BYTES of K,
    never more than the table holds."""
    p = min(CHUNK_KEYS // block_size,
            CHUNK_BYTES // (block_size * lanes * itemsize))
    return max(1, min(p, nb))


def _paged_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                  sems, slot_ref, *, scale: float, nb: int):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, P, Bs, lanes = kbuf.shape
    Hkv, G, dh = q_ref.shape[1:]
    chunk = P * Bs

    def live_blocks(s):
        # a slot of length 0 still takes one chunk, all of it masked
        return jnp.maximum(pl.cdiv(len_ref[s], Bs), 1)

    def copies(s, c, buf):
        """The DMAs of chunk ``c`` of slot ``s`` into buffer ``buf``, as
        (each, n): ``each(i, op)`` starts or waits for block ``i``'s K
        and V copies; ``n`` of the chunk's blocks are live."""
        base = s * nb + c * P

        def each(i, op):
            blk = tbl_ref[base + i]
            for hbm, dst, k in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                op(pltpu.make_async_copy(hbm.at[blk], dst.at[buf, i],
                                         sems.at[k, buf]))

        return each, jnp.minimum(live_blocks(s) - c * P, P)

    def start(s, c, buf):
        each, n = copies(s, c, buf)
        jax.lax.fori_loop(0, n, lambda i, _: each(i, lambda cp: cp.start()),
                          None)

    def wait(s, c, buf):
        each, n = copies(s, c, buf)

        @pl.when(n == P)
        def _full():
            # the P copies of each of K and V signal one semaphore: one
            # wait for the whole buffer's bytes covers them
            for hbm, dst, k in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                pltpu.make_async_copy(hbm.at[pl.ds(0, P)], dst.at[buf],
                                      sems.at[k, buf]).wait()

        @pl.when(n < P)
        def _part():
            jax.lax.fori_loop(
                0, n, lambda i, _: each(i, lambda cp: cp.wait()), None)

    @pl.when(b == 0)
    def _first():
        # rows of a slot's last chunk past its live blocks keep what an
        # earlier chunk left there: zero the buffers once, so those rows
        # are finite and their p = 0 adds exactly nothing
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        slot_ref[0] = 0
        start(0, 0, 0)

    length = len_ref[b]
    nch = pl.cdiv(live_blocks(b), P)
    q = q_ref[0]                                             # [Hkv, G, dh]

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
        k = kbuf[cur].reshape(chunk, lanes)
        v = vbuf[cur].reshape(chunk, lanes)
        cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (G, chunk), 1)
        live = cols < length
        new = []
        for h, (m_prev, l_prev, acc) in enumerate(zip(*state)):
            kh = k[:, h * dh:(h + 1) * dh]
            vh = v[:, h * dh:(h + 1) * dh]
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
    _, ls, accs = jax.lax.fori_loop(0, nch, body, state)
    for h in range(Hkv):
        o_ref[0, h] = (accs[h] / jnp.maximum(ls[h], 1e-30)).astype(
            o_ref.dtype)


def paged_decode_attention_kernel(q, pool_k, pool_v, tables, lengths, *,
                                  scale: float, interpret: bool = False):
    """q: [B, Hkv, G, dh]; pools: [N, Bs, Hkv*dh]; tables: [B, nb] int32
    block ids in sequence order; lengths: [B] int32 keys to attend, each
    at most nb*Bs. Returns the attention output [B, Hkv, G, dh]."""
    B, Hkv, G, dh = q.shape
    _, Bs, lanes = pool_k.shape
    assert lanes == Hkv * dh, "pool lanes must be Hkv*dh"
    nb = tables.shape[1]
    P = chunk_blocks(Bs, lanes, pool_k.dtype.itemsize, nb)
    buf = pltpu.VMEM((2, P, Bs, lanes), pool_k.dtype)
    kernel = functools.partial(_paged_kernel, scale=scale, nb=nb)
    qspec = pl.BlockSpec((1, Hkv, G, dh), lambda b, tbl, lens: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qspec,
        scratch_shapes=[
            buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32)],
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
      pool_k, pool_v)


def _latent_kernel(tbl_ref, len_ref, q_ref, kv_hbm, o_ref, buf, sems,
                   slot_ref, *, scale: float, nb: int, value_lanes: int):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, P, Bs, lanes = buf.shape
    H = q_ref.shape[1]
    chunk = P * Bs

    def live_blocks(s):
        # a slot of length 0 still takes one chunk, all of it masked
        return jnp.maximum(pl.cdiv(len_ref[s], Bs), 1)

    def copy(s, c, i, slot):
        return pltpu.make_async_copy(kv_hbm.at[tbl_ref[s * nb + c * P + i]],
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
            pltpu.make_async_copy(kv_hbm.at[pl.ds(0, P)], buf.at[slot],
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
    q = q_ref[0]                                             # [H, lanes]

    def body(c, state):
        m_prev, l_prev, acc = state
        cur = slot_ref[0]
        nxt = 1 - cur
        more = c + 1 < nch
        ns = jnp.where(more, b, b + 1)
        nc = jnp.where(more, c + 1, 0)

        @pl.when(ns < nslots)
        def _prefetch():
            start(ns, nc, nxt)

        wait(b, c, cur)
        slot_ref[0] = nxt
        kv = buf[cur].reshape(chunk, lanes)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (H, chunk), 1)
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
    _, l, acc = jax.lax.fori_loop(0, nch, body, state)
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
    P = chunk_blocks(Bs, lanes, pool.dtype.itemsize, nb)
    kernel = functools.partial(_latent_kernel, scale=scale, nb=nb,
                               value_lanes=value_lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, lanes), lambda b, tbl, lens: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_lanes),
                               lambda b, tbl, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, Bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32)],
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
