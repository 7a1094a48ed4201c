"""Executable serving engines: prefill and decode on real devices.

One ``Engine`` = one model instance (params + jit'd step functions) playing a
*role* (prefill / decode / colocated). Engines are role-reassignable at
runtime — that is what makes elastic scaling (serving/elastic.py) a pool-list
operation rather than a redeploy.

Decode uses continuous batching over fixed slots. Two KV layouts share the
same public surface:

- **paged** (default for the attention family): KV lives in one block
  pool ``[num_blocks, block_size, 2*Hkvp*dh]``, a token's K heads and
  then its V heads in one row (latent attention: ``[num_blocks,
  block_size, latent_lanes]``, one latent row a token), shared
  by all layers and slots, addressed through per-layer block tables
  (``serving/blocks.py`` owns the host-side refcounts). ``insert``
  scatters only the request's blocks, ``evict`` is an O(1) refcount
  decrement per block, decode attends over a pow2-bucketed window that
  tracks the *active* context instead of the full per-slot capacity, and
  the prefix cache shares blocks between entries copy-free.
- **dense** (fallback for rwkv/hybrid/sliding-window/kv-quant, or
  ``paged=False``): one ``[B_slots, capacity]``-wide cache with per-slot
  positions, as before.

Both layouts produce bit-identical greedy token streams when their
attention windows are pow2/block-aligned (tests/test_paged.py pins this
corpus-wide): the masked columns contribute exact float zeros, and the
compute cores are literally shared (``transformer._decode_attend`` /
``_chunk_attend``).

KV handoff from a prefill engine is ``insert`` — for paged engines the
payload is a ``PagedCache`` carrying only the request's own blocks
(in-process stand-in for the ICI/DCN transfer; the paper's Eq 1-2
bandwidth analysis of this hop lives in core/kv_transfer.py, which sizes
the paged hop by block-rounded length, not capacity).

Hardware is a per-engine property. On the CPU, an ``Engine`` built with a
``core.hardware.ChipConfig`` scales its measured step wall-times by the
chip's relative speed (``hardware.relative_speed``), so pools of different
chips — compute-rich prefill, bandwidth-rich decode — coexist in one
``Cluster`` and the virtual clock reflects the modelled hardware, not the
host. On an accelerator the clock is the device's own time: the engine's
chip is the detected one, its speed factor is 1, and asking for another
chip is an error. ``hardware`` names the chip class (straggler detection
groups by it) and ``capacity_weight`` is the engine's serving capacity in
reference-chip-equivalents (elastic rate matching weighs pools by it
instead of counting heads).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import ChipConfig, relative_speed
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.backends import local_chip
from repro.serving.blocks import BlockAllocator, BlockPoolExhausted
from repro.serving.common import (EngineFailure, PrefixCache,  # noqa: F401
                                  StepLog)
#   (re-exported: the failure type, prefix cache, and step log are
#    backend-agnostic — serving/common.py — so the jax-free SimEngine
#    shares them)
from repro.serving.tracing import bind_profiler, span, span_enabled


class PagedCache:
    """Paged KV-handoff payload: the request's own blocks, free-floating
    (gathered off the source pool, pool-independent). ``nbytes`` is the
    *actual* transfer size — block-rounded true length, not the slot
    capacity — which is what ``cluster.kv_bytes`` reads."""

    __slots__ = ("blocks", "length")

    def __init__(self, blocks: Dict[str, Any], length: int):
        # {"kv": [L, nb, Bs, lanes]}, rows laid out as the pool's
        self.blocks = blocks
        self.length = int(length)

    @property
    def num_blocks(self) -> int:
        """Blocks per layer."""
        return next(iter(self.blocks.values())).shape[1]

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(b.shape)) * b.dtype.itemsize
                   for b in self.blocks.values())


class PrefixBlocks:
    """A prefix-cache entry's claim on pool blocks: per-layer block ids
    [L, nb] covering ``length`` block-aligned tokens. The entry holds one
    refcount per block; sharing with other entries or in-flight requests
    is a refcount bump, never a copy."""

    __slots__ = ("ids", "length")

    def __init__(self, ids: np.ndarray, length: int):
        self.ids = ids
        self.length = int(length)


def _grow_cache(cache, capacity: int):
    """Zero-pad a trimmed dense prefix entry back to engine capacity (the
    resume path runs inside jit; the stored entry stays trimmed)."""
    out = dict(cache)
    for kk in ("k", "v"):
        C = cache[kk].shape[2]
        if C < capacity:
            pad = jnp.zeros(cache[kk].shape[:2] + (capacity - C,)
                            + cache[kk].shape[3:], cache[kk].dtype)
            out[kk] = jnp.concatenate([cache[kk], pad], axis=2)
    return out


def decode_impl(cfg: ModelConfig) -> str:
    """Attention of the paged decode step: a Pallas kernel on a TPU for
    the configs it takes, else XLA, which is also the reference on the
    CPU. The GQA kernel takes no padded heads and a head size of whole
    128-lane tiles; the latent kernel, values (c_kv) of whole tiles."""
    if jax.default_backend() != "tpu":
        return "xla"
    if cfg.mla:
        return "pallas" if cfg.kv_lora_rank % 128 == 0 else "xla"
    if cfg.padded_heads == cfg.num_heads and cfg.dh % 128 == 0:
        return "pallas"
    return "xla"


class Engine:
    """One model instance. Thread-unsafe by design (driven by Orchestrator)."""

    backend = "real"

    def __init__(self, engine_id: int, cfg: ModelConfig, params,
                 *, slots: int = 8, capacity: int = 256,
                 chunk_size: int = 0, chip: Optional[ChipConfig] = None,
                 speed_factor: Optional[float] = None,
                 step_history: int = 1024, block_size: int = 8,
                 paged: Optional[bool] = None,
                 pool_blocks: Optional[int] = None):
        self.engine_id = engine_id
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.capacity = capacity
        self.chunk_size = chunk_size
        self.healthy = True
        self.clock = 0.0                       # engine-local clock (s)
        self.step_times = StepLog(step_history)
        self._slow_factor = 1.0                # straggler injection (tests)
        # hardware class: on the CPU, measured wall-times scale by
        # 1/relative_speed so a v5p engine's virtual steps are ~2.8x
        # shorter than a v5e's; on an accelerator they are device time
        device_chip = local_chip()
        if device_chip is not None:
            if chip is not None and chip.name != device_chip.name:
                raise ValueError(
                    f"engine {engine_id}: chip {chip.name} requested, but "
                    f"this process drives a {device_chip.name}; device "
                    "time is not rescaled to another chip")
            if speed_factor not in (None, 1.0):
                raise ValueError(f"engine {engine_id}: speed_factor "
                                 f"{speed_factor} on a {device_chip.name}; "
                                 "device time is not rescaled")
            chip, speed_factor = device_chip, 1.0
        self.chip = chip
        self.hardware = chip.name if chip is not None else "uniform"
        if speed_factor is not None:
            self.speed_factor = speed_factor
        else:
            self.speed_factor = (1.0 / relative_speed(chip)
                                 if chip is not None else 1.0)

        if paged is None:
            self.paged = T.supports_paged(cfg)
        else:
            if paged and not T.supports_paged(cfg):
                raise ValueError(f"paged KV unsupported for {cfg.block}/"
                                 f"kv_quant={cfg.kv_quant}/"
                                 f"sliding_window={cfg.sliding_window}")
            self.paged = paged
        self.block_size = block_size
        if chunk_size and self.paged:
            assert chunk_size % block_size == 0, \
                "paged chunked prefill needs chunk_size % block_size == 0"

        # host spans (serving/tracing.py) go to the profiler's clock
        bind_profiler(jax.profiler.TraceAnnotation)
        # the dense layout's decode step always attends in XLA
        self.decode_impl = impl = decode_impl(cfg) if self.paged else "xla"

        # each jitted step is a named function, so its HLO module and
        # device ops read jit(<name>) in a profile; prefill attends in XLA
        def prefill_full(p, i):
            return T.prefill_full(p, cfg, i, capacity=capacity)
        self._prefill = jax.jit(prefill_full)
        # jitted chunked-prefill wrappers, keyed (chunk, has_base_cache):
        # building a fresh jax.jit per call would discard jit's trace cache
        # and recompile on every request.
        self._chunked_fns: Dict[Tuple[int, bool], Any] = {}
        self._free = list(range(slots))
        self.slot_req: Dict[int, Any] = {}

        if self.paged:
            Bs = block_size
            Lr = cfg.num_layers
            self._nb_max = -(-capacity // Bs)
            if pool_blocks is None:
                # full decode occupancy + in-flight prefill + prefix headroom
                pool_blocks = 1 + Lr * self._nb_max * (slots + 4)
            self.pool = T.init_block_pool(cfg, pool_blocks, Bs)
            self._alloc = BlockAllocator(pool_blocks)
            self._tables = np.zeros((Lr, slots, self._nb_max), np.int32)
            self._pos = np.zeros((slots,), np.int32)
            self.cache = None
            self.prefix_cache = (
                PrefixCache(chunk_size, on_evict=self._release_entry)
                if chunk_size and cfg.block == "attn" else None)

            def decode_step_paged(p, pool, tbl, pos, t):
                return T.decode_step_paged(p, cfg, pool, tbl, pos, t,
                                           impl=impl)
            self._decode_paged = jax.jit(decode_step_paged,
                                         donate_argnums=(1,))
            self._scatter = jax.jit(T.scatter_blocks, donate_argnums=(0,))
            self._gather = jax.jit(T.gather_blocks)
            self._prefill_payload = jax.jit(self._prefill_payload_impl)
            self._paged_chunked_fns: Dict[int, Any] = {}
        else:
            self.prefix_cache = (PrefixCache(chunk_size) if chunk_size
                                 and cfg.block == "attn" else None)

            def decode_step(p, c, t):
                return T.decode_step(p, cfg, c, t)
            self._decode = jax.jit(decode_step)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
            self.cache = T.init_cache(cfg, slots, capacity)

    # ---- fault/straggler injection hooks (used by tests & demos) -------

    def fail(self):
        self.healthy = False

    def slow_down(self, factor: float):
        self._slow_factor = factor

    @property
    def capacity_weight(self) -> float:
        """Serving capacity in reference-chip (v5e) equivalents — what the
        elastic rate matcher sums instead of counting engine heads."""
        return 1.0 / self.speed_factor

    def describe(self) -> Dict[str, Any]:
        """Static metadata for trace track labels (serving.tracing)."""
        return {"engine_id": self.engine_id, "backend": self.backend,
                "hardware": self.hardware, "slots": self.slots,
                "capacity": self.capacity, "paged": self.paged,
                "block_size": self.block_size if self.paged else 0,
                "speed_factor": self.speed_factor,
                "capacity_weight": self.capacity_weight}

    def _tick(self, t0: float):
        dt = ((time.perf_counter() - t0) * self.speed_factor
              * self._slow_factor)
        self.clock += dt
        self.step_times.append(dt)
        return dt

    def _check(self):
        if not self.healthy:
            raise EngineFailure(f"engine {self.engine_id} is down")

    # ---- block-pool bookkeeping (paged) --------------------------------

    def _release_entry(self, payload: PrefixBlocks):
        """PrefixCache on_evict: drop the entry's refcounts (a block goes
        back to the free list only when no other entry/request holds it)."""
        self._alloc.free(payload.ids.ravel().tolist())

    def _reserve(self, n: int):
        """Ensure n blocks are allocatable, reclaiming LRU prefix entries
        under pressure (the paged analogue of cache-capacity eviction)."""
        while not self._alloc.can_alloc(n):
            if self.prefix_cache is None or not self.prefix_cache.pop_lru():
                raise BlockPoolExhausted(
                    f"engine {self.engine_id}: need {n} blocks, "
                    f"{self._alloc.num_free} free and no prefix entries "
                    f"left to evict")

    # ---- prefill role ---------------------------------------------------

    def _prefill_payload_impl(self, p, inputs):
        """Full prefill -> (logits, handoff blocks). The cache is laid out
        as the pool's rows and reshaped to [L, nb, Bs, lanes] blocks
        (block-padded true length — never the slot capacity); logits are
        computed before any padding, so they match the dense engine's
        bit-for-bit."""
        logits, cache = T.prefill_full(p, self.cfg, inputs)
        S = inputs["tokens"].shape[1]
        Bs = self.block_size
        Sb = -(-S // Bs) * Bs
        rows = (cache["kv"] if self.cfg.mla
                else T.kv_rows(cache["k"], cache["v"]))[:, 0]  # [L, S, lanes]
        if Sb > S:
            rows = jnp.pad(rows, ((0, 0), (0, Sb - S), (0, 0)))
        return logits, {"kv": rows.reshape(rows.shape[0], Sb // Bs, Bs, -1)}

    def prefill(self, prompt: np.ndarray) -> Tuple[int, Any]:
        """Full prefill of one prompt; returns (first_token, payload)."""
        self._check()
        with span("serve.prefill"):
            t0 = time.perf_counter()
            with span("serve.prefill.dispatch"):
                inputs = {"tokens": jnp.asarray(prompt)[None, :]}
                if self.paged:
                    logits, blocks = self._prefill_payload(self.params,
                                                           inputs)
                    cache = PagedCache(blocks, len(prompt))
                else:
                    logits, cache = self._prefill(self.params, inputs)
            with span("serve.prefill.sample"):
                tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
                jax.block_until_ready(tok)
            self._tick(t0)
        return tok, cache

    def prefill_chunked(self, prompt: np.ndarray, chunk: int,
                        on_chunk=None) -> Tuple[int, Any]:
        """Sarathi-style chunked prefill (the executable piggyback path);
        on_chunk(i, n) fires after each chunk (lets a co-located engine
        interleave decode steps between chunks). Reuses the longest cached
        prompt prefix when a PrefixCache is attached (§7 KV reuse)."""
        self._check()
        if self.paged:
            return self._prefill_chunked_paged(prompt, chunk, on_chunk)
        S = len(prompt)
        pad = (-S) % chunk
        toks = np.pad(prompt, (0, pad), constant_values=0)
        start, base_cache = 0, None
        if self.prefix_cache is not None:
            base_cache, start = self.prefix_cache.lookup(prompt)
        t0 = time.perf_counter()
        inputs = {"tokens": jnp.asarray(toks)[None, :]}
        if base_cache is not None:
            logits, cache = self._chunked_fn(chunk, True)(
                self.params, inputs, base_cache, start=start)
        else:
            logits, cache = self._chunked_fn(chunk, False)(
                self.params, inputs)
        tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
        self._tick(t0)
        if self.prefix_cache is not None:
            # store only the chunk-aligned *true* prompt prefix — the
            # compute cache runs to the padded length (and, grown, to the
            # slot capacity), but pad-token KV must never be reusable and
            # entries must not pin capacity-sized tensors
            n = (S // chunk) * chunk
            if n > 0:
                entry = {k: (v if k == "pos" else v[:, :, :n])
                         for k, v in cache.items()}
                entry["pos"] = jnp.full_like(cache["pos"], n)
                self.prefix_cache.insert(prompt, entry)
        if on_chunk:
            for i in range((S - start + pad) // chunk):
                on_chunk(i, max((S - start + pad) // chunk, 1))
        return tok, cache

    def _prefill_chunked_paged(self, prompt: np.ndarray, chunk: int,
                               on_chunk=None) -> Tuple[int, Any]:
        """Paged chunked prefill: append chunk KV straight into this
        request's blocks (no dense B=1 cache), share prefix blocks through
        the refcounted PrefixCache, gather only the request's blocks as
        the handoff payload."""
        Bs = self.block_size
        Lr = self.cfg.num_layers
        assert chunk % Bs == 0, "chunk must be block-aligned"
        S = len(prompt)
        pad = (-S) % chunk
        Sp = S + pad
        with span("serve.prefill"):
            with span("serve.prefill.blocks"):
                toks = np.pad(prompt, (0, pad), constant_values=0)
                start, entry = 0, None
                if self.prefix_cache is not None:
                    entry, start = self.prefix_cache.lookup(prompt)
                nb_total = Sp // Bs
                nb_prefix = start // Bs
                tbl = np.zeros((Lr, nb_total), np.int32)
                self._reserve(Lr * (nb_total - nb_prefix))
                if entry is not None:
                    tbl[:, :nb_prefix] = entry.ids[:, :nb_prefix]
                    self._alloc.ref(tbl[:, :nb_prefix].ravel().tolist())
                fresh = self._alloc.alloc(Lr * (nb_total - nb_prefix))
                tbl[:, nb_prefix:] = np.asarray(fresh, np.int32).reshape(
                    Lr, nb_total - nb_prefix)
            t0 = time.perf_counter()
            with span("serve.prefill.dispatch"):
                inputs = {"tokens": jnp.asarray(toks)[None, :]}
                tbl_j = jnp.asarray(tbl)
                logits, self.pool = self._paged_chunked_fn(chunk)(
                    self.params, inputs, self.pool, tbl_j, start=start)
            with span("serve.prefill.kv_out"):
                blocks = self._gather(self.pool, tbl_j)
            with span("serve.prefill.sample"):
                tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
            self._tick(t0)
            with span("serve.prefill.kv_out"):
                payload = PagedCache(jax.tree.map(np.asarray, blocks), Sp)
            # prefix entry keeps the chunk-aligned true prefix; everything
            # else (pad blocks) goes straight back to the pool. The
            # request's refcounts transfer to the entry — the payload above
            # is a copy.
            with span("serve.prefill.blocks"):
                n = (S // chunk) * chunk
                nbk = n // Bs
                if self.prefix_cache is not None and nbk > 0:
                    if nb_total > nbk:
                        self._alloc.free(tbl[:, nbk:].ravel().tolist())
                    self.prefix_cache.insert(prompt,
                                             PrefixBlocks(tbl[:, :nbk], n))
                else:
                    self._alloc.free(tbl.ravel().tolist())
        if on_chunk:
            for i in range((S - start + pad) // chunk):
                on_chunk(i, max((S - start + pad) // chunk, 1))
        return tok, payload

    def _chunked_fn(self, chunk: int, has_base: bool):
        """Cached jitted chunked-prefill callable. ``start`` stays a static
        argname (it drives the Python chunk loop), so jit's own trace cache
        keys on (start, shapes) and repeated prompts hit compiled code."""
        fn = self._chunked_fns.get((chunk, has_base))
        if fn is None:
            if has_base:
                def prefill_chunked_resume(p, i, c, start):
                    return T.prefill_chunked(
                        p, self.cfg, i, chunk, capacity=self.capacity,
                        cache=_grow_cache(c, self.capacity), start=start)
                fn = jax.jit(prefill_chunked_resume,
                             static_argnames=("start",))
            else:
                def prefill_chunked(p, i):
                    return T.prefill_chunked(p, self.cfg, i, chunk,
                                             capacity=self.capacity)
                fn = jax.jit(prefill_chunked)
            self._chunked_fns[(chunk, has_base)] = fn
        return fn

    def _paged_chunked_fn(self, chunk: int):
        """Cached jitted paged chunked-prefill callable (pool donated:
        blocks are appended in place, the pool is never copied)."""
        fn = self._paged_chunked_fns.get(chunk)
        if fn is None:
            def prefill_chunked_paged(p, i, pool, tbl, start):
                return T.prefill_chunked_paged(p, self.cfg, i, chunk, pool,
                                               tbl, start=start)
            fn = jax.jit(prefill_chunked_paged, static_argnames=("start",),
                         donate_argnums=(2,))
            self._paged_chunked_fns[chunk] = fn
        return fn

    # ---- decode role ----------------------------------------------------

    def _insert_impl(self, dest, src, slot, length):
        """Scatter a B=1 prefill cache into decode slot `slot` (dense)."""
        out = dict(dest)
        for k in dest:
            if k == "pos":
                out[k] = dest[k].at[slot].set(length)
            elif k in ("k", "v"):
                Cs = src[k].shape[2]
                Cd = dest[k].shape[2]
                pad = Cd - Cs
                row = src[k][:, 0]
                if pad > 0:
                    row = jnp.concatenate(
                        [row, jnp.zeros((row.shape[0], pad) + row.shape[2:],
                                        row.dtype)], axis=1)
                elif pad < 0:
                    row = row[:, :Cd]
                out[k] = dest[k].at[:, slot].set(row)
            else:
                out[k] = dest[k].at[:, slot].set(src[k][:, 0])
        return out

    def has_free_slot(self) -> bool:
        return bool(self._free)

    @property
    def active(self) -> int:
        return len(self.slot_req)

    def insert(self, req, cache_b1) -> int:
        """KV handoff: place a prefilled request into a free slot. Paged
        engines scatter only the request's blocks (O(request), not
        O(capacity)); dense engines scatter a capacity-wide row."""
        self._check()
        with span("serve.insert"):
            slot = self._free.pop()
            if self.paged:
                self._insert_paged(slot, cache_b1)
            else:
                if isinstance(cache_b1, PagedCache):
                    raise TypeError("dense engine got a paged handoff "
                                    "payload; mixed-layout fleets are "
                                    "unsupported")
                length = cache_b1["pos"][0]
                src = {k: v for k, v in cache_b1.items() if k != "pos"}
                self.cache = self._insert(self.cache, src, slot, length)
        self.slot_req[slot] = req
        req.slot = slot
        req.engine_id = self.engine_id
        return slot

    def _insert_paged(self, slot: int, payload: PagedCache):
        """Claim blocks for the payload in ``slot``'s table, then scatter
        its KV into them (the scatter donates the pool: in place)."""
        if not isinstance(payload, PagedCache):
            raise TypeError("paged engine got a dense handoff payload; "
                            "mixed-layout fleets are unsupported")
        nbk = payload.num_blocks
        Lr = self.cfg.num_layers
        with span("serve.insert.blocks"):
            try:
                self._reserve(Lr * nbk)
                ids = np.asarray(self._alloc.alloc(Lr * nbk),
                                 np.int32).reshape(Lr, nbk)
            except BlockPoolExhausted:
                self._free.append(slot)
                raise
            self._tables[:, slot, :] = 0
            self._tables[:, slot, :nbk] = ids
            self._pos[slot] = payload.length
        with span("serve.insert.upload"):
            self.pool = self._scatter(
                self.pool, jnp.asarray(ids),
                {k: jnp.asarray(v) for k, v in payload.blocks.items()})

    def evict(self, slot: int):
        """Free a slot. Paged: each of the request's blocks is one
        refcount decrement — no tensor traffic at all."""
        req = self.slot_req.pop(slot, None)
        if req is not None:
            req.slot = None
        if self.paged:
            row = self._tables[:, slot, :]
            live = row[row != 0]
            if live.size:
                self._alloc.free(live.tolist())
            self._tables[:, slot, :] = 0
            self._pos[slot] = 0
        self._free.append(slot)

    def decode_step(self, tokens_by_slot: Dict[int, int]) -> Dict[int, int]:
        """One token for every active slot. Returns slot -> next token."""
        self._check()
        if self.paged:
            return self._decode_step_paged(tokens_by_slot)
        t0 = time.perf_counter()
        toks = np.zeros((self.slots,), np.int32)
        for s, t in tokens_by_slot.items():
            toks[s] = t
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits[:, :self.cfg.vocab_size], axis=-1))
        jax.block_until_ready(nxt)
        self._tick(t0)
        return {s: int(nxt[s]) for s in tokens_by_slot}

    def _decode_step_paged(self, tokens_by_slot: Dict[int, int]):
        Bs = self.block_size
        Lr = self.cfg.num_layers
        if self.decode_impl == "pallas":
            # the kernel reads each slot's live blocks, whatever the
            # table's width: one step for every context
            nb = self._nb_max
        else:
            # pow2-bucketed window over the *active* context: the table
            # slice (and therefore the attention width) tracks what is
            # live, so jit retraces at most log2(nb_max) times while short
            # contexts never pay full-capacity attention
            mx = max(int(self._pos[s]) for s in tokens_by_slot)
            nb = 1
            while nb * Bs <= mx:
                nb *= 2
            nb = min(nb, self._nb_max)
        with span("serve.decode",
                  **self._decode_counters(tokens_by_slot, nb)) as sp:
            # grow: a slot whose next write crosses a block boundary gets a
            # fresh block per layer *before* the jit'd step (O(1) host work)
            with span("serve.decode.blocks"):
                for s in tokens_by_slot:
                    bi = int(self._pos[s]) // Bs
                    if bi < self._nb_max and self._tables[0, s, bi] == 0:
                        self._reserve(Lr)
                        self._tables[:, s, bi] = self._alloc.alloc(Lr)
            t0 = time.perf_counter()
            with span("serve.decode.upload"):
                toks = np.zeros((self.slots,), np.int32)
                for s, t in tokens_by_slot.items():
                    toks[s] = t
                tables = jnp.asarray(self._tables[:, :, :nb])
                pos = jnp.asarray(self._pos)
                toks = jnp.asarray(toks)
            with span("serve.decode.dispatch"):
                logits, self.pool, out = self._decode_paged(
                    self.params, self.pool, tables, pos, toks)
            with span("serve.decode.sync"):
                # the routing hits come back in the tokens' transfer
                nxt, hits = jax.device_get(
                    (jnp.argmax(logits[:, :self.cfg.vocab_size], axis=-1),
                     out.get("hits")))
            if sp is not None and hits is not None:
                sp.set_metadata(**self._expert_counters(hits,
                                                        tokens_by_slot))
            for s in tokens_by_slot:
                self._pos[s] += 1
            self._tick(t0)
        return {s: int(nxt[s]) for s in tokens_by_slot}

    def _decode_counters(self, tokens_by_slot: Dict[int, int],
                         nb: int) -> Dict[str, int]:
        """The ``serve.decode`` span's counters, built only while a
        profiler records: the width in blocks of the table the step is
        given (its window), slots, and the keys the batch attends (each
        active slot's position plus the token it writes)."""
        if not span_enabled():
            return {}
        live = sum(int(self._pos[s]) + 1 for s in tokens_by_slot)
        return {"window_blocks": nb, "slots": self.slots,
                "live_tokens": live}

    @staticmethod
    def _expert_counters(hits: np.ndarray,
                         tokens_by_slot: Dict[int, int]) -> Dict[str, int]:
        """The ``serve.decode`` span's counters of a held-expert MoE model,
        from the step's routing hits [MoE layers, slots, held experts] over
        the active slots, summed over the MoE layers: tokens the held
        experts computed, held experts with at least one token, and tokens
        through the MoE layers (each layer's router and shared expert)."""
        act = hits[:, sorted(tokens_by_slot)]
        return {"expert_tokens": int(act.sum()),
                "experts_touched": int(act.any(axis=1).sum()),
                "moe_tokens": int(act.shape[0] * act.shape[1])}

    @property
    def mean_step_s(self) -> float:
        if not self.step_times:
            return 0.0
        return float(np.mean(self.step_times[-50:]))
