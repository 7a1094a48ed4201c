"""Paged KV cache: golden equivalence vs the dense layout + pool invariants.

The contract (docs/kernels.md): with block-aligned power-of-two attention
widths, the paged engine's greedy token streams are *byte-identical* to
the dense engine's — masked columns contribute exact float zeros and both
layouts share the same attention cores (``transformer._decode_attend`` /
``_chunk_attend``). Equality is pinned over the full checked-in trace
corpus (sha256 of every request's stream), and the host-side block pool
must account for every block: nothing leaks after evict, refcounted
prefix shares free only at refcount zero.

Capacities here are rounded to powers of two on *both* engines: pow2
attention widths are mutually bit-identical, while a non-pow2 dense width
differs from a pow2 paged window by reduction-tree noise (~1e-7) — real
float behavior, not a bug, and why the equality claim is scoped to
block-aligned capacities.
"""
import hashlib
import pathlib

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.blocks import BlockAllocator, BlockPoolExhausted
from repro.serving.cluster import Cluster, kv_bytes
from repro.serving.common import StepLog
from repro.serving.engine import Engine, PagedCache, PrefixBlocks
from repro.serving.policies import PriorityScheduler
from repro.serving.request import Request
from repro.workloads import TraceReplay

TRACE_DIR = pathlib.Path(__file__).parent / "data" / "traces"
TRACES = ("burst", "diurnal", "sessions", "tiers", "fleet_diurnal")
VOCAB = 97

CFG = ModelConfig(name="trace-tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=VOCAB,
                  remat=False, logits_chunk=32, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _serve(name, params, base_id, paged):
    """One fresh-cluster serve of a trace at pow2 capacity; returns
    ({rid: stream}, engines)."""
    replay = TraceReplay(TRACE_DIR / f"{name}.jsonl", vocab=VOCAB)
    cap = _pow2(replay.max_context() + 8)
    sched = PriorityScheduler() if name == "tiers" else None
    engines = [Engine(base_id, CFG, params, slots=4, capacity=cap,
                      paged=paged),
               Engine(base_id + 1, CFG, params, slots=4, capacity=cap,
                      paged=paged)]
    cl = Cluster({"prefill": [engines[0]], "decode": [engines[1]]},
                 **({"scheduler": sched} if sched else {}))
    m = cl.serve(replay, max_wall_s=600)
    assert m["completed"] == len(replay.requests)
    return {r.rid: list(r.output) for r in replay.requests}, engines


def _digest(streams):
    h = hashlib.sha256()
    for rid in sorted(streams):
        h.update(np.asarray(streams[rid], np.int64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", TRACES)
def test_paged_vs_dense_streams_identical(name, params):
    """Golden equivalence: the paged engine must reproduce the dense
    engine's token streams byte-for-byte on every corpus trace — and the
    block pool must be fully drained once every request completed."""
    dense, _ = _serve(name, params, base_id=0, paged=False)
    paged, engines = _serve(name, params, base_id=10, paged=True)
    assert dense.keys() == paged.keys()
    assert _digest(dense) == _digest(paged), \
        f"{name}: paged streams diverged from dense"
    for e in engines:                       # no leaked blocks after evict
        assert e._alloc.used == 0, (e.engine_id, e._alloc.used)


def test_insert_evict_returns_blocks(params):
    """Every insert allocates exactly the payload's blocks; evict returns
    all of them (O(1) refcount decrements, no tensor traffic)."""
    src = Engine(0, CFG, params, slots=2, capacity=64, paged=True)
    dst = Engine(1, CFG, params, slots=2, capacity=64, paged=True)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, VOCAB, 21).astype(np.int32)
    tok, cache = src.prefill(prompt)
    assert isinstance(cache, PagedCache) and cache.length == 21
    assert src._alloc.used == 0             # full prefill never touches pool
    free0 = dst._alloc.num_free
    slot = dst.insert(Request(rid=0, prompt=prompt, osl=4), cache)
    assert set(cache.blocks) == {"kv"}      # a row: K heads, then V heads
    nbk = cache.blocks["kv"].shape[1]       # ceil(21/8) = 3 blocks/layer
    assert nbk == 3
    assert cache.blocks["kv"].shape[-1] == 2 * CFG.padded_kv_heads * CFG.dh
    assert dst._alloc.used == CFG.num_layers * nbk
    out = dst.decode_step({slot: tok})      # crosses 21 -> 24: same block
    dst.decode_step({slot: out[slot]})
    dst.evict(slot)
    assert dst._alloc.used == 0 and dst._alloc.num_free == free0


def test_prefix_blocks_shared_and_freed_at_zero_refcount(params):
    """Two prefix entries sharing leading blocks: evicting one keeps the
    shared blocks resident (refcount), evicting both frees everything."""
    eng = Engine(0, CFG, params, slots=2, capacity=64, chunk_size=8,
                 paged=True)
    rng = np.random.default_rng(1)
    a = rng.integers(1, VOCAB, 24).astype(np.int32)
    b = np.concatenate([a[:16], rng.integers(1, VOCAB, 8).astype(np.int32)])
    eng.prefill_chunked(a, 8)
    hits0 = eng.prefix_cache.hits
    eng.prefill_chunked(b, 8)               # shares a's first 16 tokens
    assert eng.prefix_cache.hits == hits0 + 1
    assert len(eng.prefix_cache) == 2
    # entry(a): 3 blocks/layer; entry(b): 3/layer, first 2 shared with a
    used_both = eng._alloc.used
    assert used_both == CFG.num_layers * 4  # 3 + 1 distinct per layer
    shared = eng.prefix_cache.lookup(a)[0].ids[:, :2]
    for blk in shared.ravel().tolist():
        assert eng._alloc.refcount(blk) == 2
    assert eng.prefix_cache.pop_lru()       # evicts a (LRU)
    assert eng._alloc.used == CFG.num_layers * 3   # b keeps shared blocks
    for blk in shared.ravel().tolist():
        assert eng._alloc.refcount(blk) == 1
    assert eng.prefix_cache.pop_lru()
    assert eng._alloc.used == 0             # zero refcount -> freed


def test_pool_pressure_reclaims_prefix_lru(params):
    """Block-pool exhaustion evicts prefix LRU entries before failing; a
    pool too small even after reclaim raises BlockPoolExhausted."""
    eng = Engine(0, CFG, params, slots=1, capacity=64, chunk_size=8,
                 paged=True, pool_blocks=1 + CFG.num_layers * 3 * 3)
    rng = np.random.default_rng(2)
    for i in range(4):                      # each entry: 3 blocks/layer
        eng.prefill_chunked(rng.integers(1, VOCAB, 24).astype(np.int32), 8)
    assert len(eng.prefix_cache) < 4        # LRU reclaim kept the pool fed
    tiny = Engine(1, CFG, params, slots=1, capacity=64, chunk_size=8,
                  paged=True, pool_blocks=1 + CFG.num_layers)
    with pytest.raises(BlockPoolExhausted):
        tiny.prefill_chunked(rng.integers(1, VOCAB, 24).astype(np.int32), 8)


def test_prefix_entry_trimmed_to_true_length(params):
    """Satellite regression: prefix entries must store the chunk-aligned
    *true* prompt prefix, not the capacity/padded-width compute cache —
    on both layouts."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, VOCAB, 17).astype(np.int32)   # pads to 24
    dense = Engine(0, CFG, params, slots=2, capacity=64, chunk_size=8,
                   paged=False)
    dense.prefill_chunked(prompt, 8)
    entry = next(iter(dense.prefix_cache._entries.values()))
    assert entry["k"].shape[2] == 16        # floor(17/8)*8, not 64
    assert int(entry["pos"][0]) == 16
    paged = Engine(1, CFG, params, slots=2, capacity=64, chunk_size=8,
                   paged=True)
    paged.prefill_chunked(prompt, 8)
    pentry = paged.prefix_cache.lookup(prompt)[0]
    assert isinstance(pentry, PrefixBlocks)
    assert pentry.length == 16 and pentry.ids.shape == (CFG.num_layers, 2)
    # pad-token KV is not resident: only 2 blocks/layer are held
    assert paged._alloc.used == CFG.num_layers * 2


def test_trimmed_prefix_resume_matches_fresh_serve(params):
    """Resuming from a trimmed entry must reproduce the no-reuse stream
    exactly (the trim changes storage, not results)."""
    rng = np.random.default_rng(4)
    base = rng.integers(1, VOCAB, 24).astype(np.int32)
    follow = np.concatenate([base, rng.integers(1, VOCAB, 9)
                             .astype(np.int32)])
    for paged in (False, True):
        warm = Engine(0, CFG, params, slots=2, capacity=64, chunk_size=8,
                      paged=paged)
        warm.prefill_chunked(base, 8)
        tok_w, _ = warm.prefill_chunked(follow, 8)
        assert warm.prefix_cache.hits == 1
        cold = Engine(1, CFG, params, slots=2, capacity=64, chunk_size=8,
                      paged=paged)
        tok_c, _ = cold.prefill_chunked(follow, 8)
        assert tok_w == tok_c, f"paged={paged}"


def test_paged_payload_kv_bytes_is_block_rounded(params):
    """cluster.kv_bytes on a PagedCache charges block-rounded true length,
    not the capacity-padded dense tensors."""
    eng = Engine(0, CFG, params, slots=2, capacity=256, paged=True)
    prompt = np.arange(1, 22, dtype=np.int32)      # 21 tokens -> 3 blocks
    _tok, cache = eng.prefill(prompt)
    per_tok = (2 * CFG.num_layers * CFG.padded_kv_heads * CFG.dh
               * np.dtype(np.float32).itemsize)
    assert kv_bytes(cache) == 3 * 8 * per_tok
    dense = Engine(1, CFG, params, slots=2, capacity=256, paged=False)
    _tok, dcache = dense.prefill(prompt)
    assert kv_bytes(dcache) == 256 * per_tok       # capacity-padded
    assert kv_bytes(cache) < kv_bytes(dcache)


def test_paged_handoff_between_engines_decodes_as_the_source(params):
    """A payload gathered off one paged engine's pool (chunked prefill,
    ``gather_blocks``) and scattered into another's at other block ids
    (``scatter_blocks``) decodes the tokens the source engine decodes
    from it; it carries K and V in one array of the bytes two did."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, VOCAB, 21).astype(np.int32)
    src = Engine(0, CFG, params, slots=2, capacity=64, chunk_size=8,
                 paged=True)
    dst = Engine(1, CFG, params, slots=2, capacity=64, paged=True)
    tok, payload = src.prefill_chunked(prompt, 8)
    assert set(payload.blocks) == {"kv"}
    nbk = payload.num_blocks                # 24 padded tokens: 3 blocks
    assert payload.nbytes == (2 * CFG.num_layers * nbk * 8
                              * CFG.padded_kv_heads * CFG.dh * 4)
    # another request first, so the payload lands on other block ids
    _t, other = dst.prefill(rng.integers(1, VOCAB, 11).astype(np.int32))
    dst.insert(Request(rid=0, prompt=prompt[:11], osl=4), other)

    def decode(eng, rid):
        slot = eng.insert(Request(rid=rid, prompt=prompt, osl=6), payload)
        out, t = [], tok
        for _ in range(6):
            t = eng.decode_step({slot: t})[slot]
            out.append(t)
        return out, eng._tables[:, slot, :nbk].copy()

    want, src_ids = decode(src, 1)
    got, dst_ids = decode(dst, 2)
    assert not np.array_equal(src_ids, dst_ids)
    assert got == want


def test_mixed_layout_handoff_rejected(params):
    dense = Engine(0, CFG, params, slots=2, capacity=64, paged=False)
    paged = Engine(1, CFG, params, slots=2, capacity=64, paged=True)
    prompt = np.arange(1, 20, dtype=np.int32)
    _t, dc = dense.prefill(prompt)
    _t, pc = paged.prefill(prompt)
    with pytest.raises(TypeError):
        paged.insert(Request(rid=0, prompt=prompt, osl=2), dc)
    with pytest.raises(TypeError):
        dense.insert(Request(rid=1, prompt=prompt, osl=2), pc)
    assert paged.has_free_slot() and dense.has_free_slot()


def test_block_allocator_refcounts():
    a = BlockAllocator(8)                   # block 0 reserved (trash)
    ids = a.alloc(3)
    assert a.used == 3 and 0 not in ids
    a.ref(ids[:1])
    a.free(ids)                             # drops one ref on each
    assert a.used == 1                      # ids[0] still held
    a.free(ids[:1])
    assert a.used == 0 and a.num_free == 7
    with pytest.raises(ValueError):
        a.free(ids[:1])                     # double free
    with pytest.raises(ValueError):
        a.ref([5])                          # ref of unallocated block


def test_engine_step_times_bounded(params):
    """Engine.step_times is a StepLog ring: memory stays bounded while
    absolute indices (cluster reads step_times[n0]) and the mean_step_s
    window keep working."""
    eng = Engine(0, CFG, params, slots=1, capacity=32, step_history=4)
    assert isinstance(eng.step_times, StepLog)
    prompt = np.arange(1, 9, dtype=np.int32)
    tok, cache = eng.prefill(prompt)
    slot = eng.insert(Request(rid=0, prompt=prompt, osl=16), cache)
    for _ in range(16):
        tok = eng.decode_step({slot: tok})[slot]
    assert len(eng.step_times) == 17        # absolute count, not retained
    assert len(eng.step_times._buf) <= 8    # ring keeps N..2N
    assert eng.step_times[len(eng.step_times) - 1] == eng.step_times[-1]
    assert eng.mean_step_s > 0.0
    with pytest.raises(IndexError):         # trimmed prefix is gone
        eng.step_times[0]


KERNEL_CFG = ModelConfig(name="kernel-tiny", family="dense", num_layers=1,
                         d_model=256, num_heads=2, num_kv_heads=1, d_ff=64,
                         vocab_size=VOCAB, remat=False, dtype="bfloat16")


def _takes_kernel(fn, *args, **kwargs) -> bool:
    return "pallas_call" in str(fn.trace(*args, **kwargs).jaxpr)


@pytest.mark.parametrize("backend,pad_heads_to,impl", [
    ("cpu", 0, "xla"),          # the CPU runs the XLA reference
    ("tpu", 0, "pallas"),
    ("tpu", 4, "xla"),          # padded heads: a config the kernel refuses
])
def test_engine_chooses_the_paged_decode_kernel(monkeypatch, backend,
                                                pad_heads_to, impl):
    """A paged engine attends in its decode step through the Pallas
    kernel on a TPU, for a config without padded heads and with a
    lane-aligned head size; its prefill steps always attend in XLA."""
    import dataclasses
    cfg = dataclasses.replace(KERNEL_CFG, pad_heads_to=pad_heads_to)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = Engine(0, cfg, params, slots=2, capacity=32, chunk_size=8)
    assert eng.decode_impl == impl
    z = np.zeros((2,), np.int32)
    assert _takes_kernel(eng._decode_paged, params, eng.pool,
                         eng._tables[:, :, :2], z, z) == (impl == "pallas")
    tokens = {"tokens": np.zeros((1, 16), np.int32)}
    tbl = np.zeros((cfg.num_layers, 2), np.int32)
    assert not _takes_kernel(eng._prefill_payload, params, tokens)
    assert not _takes_kernel(eng._paged_chunked_fn(8), params, tokens,
                             eng.pool, tbl, start=0)
    dense = Engine(1, cfg, params, slots=2, capacity=32, paged=False)
    assert dense.decode_impl == "xla"


def test_engine_chooses_the_latent_decode_kernel(monkeypatch):
    """A latent-attention engine attends in its decode step through the
    latent paged kernel on a TPU (c_kv of whole 128-lane tiles) and in
    XLA elsewhere; its pool is one array of latent rows."""
    import dataclasses
    cfg = dataclasses.replace(KERNEL_CFG, num_kv_heads=2, q_lora_rank=32,
                              kv_lora_rank=128, qk_nope_dim=32,
                              qk_rope_dim=64, v_head_dim=32)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    z = np.zeros((2,), np.int32)
    for backend, impl in (("cpu", "xla"), ("tpu", "pallas")):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        eng = Engine(0, cfg, params, slots=2, capacity=32, chunk_size=8)
        assert eng.decode_impl == impl
        assert set(eng.pool) == {"kv"}
        assert eng.pool["kv"].shape[-1] == 256       # 192 lanes used
        assert _takes_kernel(eng._decode_paged, params, eng.pool,
                             eng._tables[:, :, :2], z, z) == (
                                 impl == "pallas")
