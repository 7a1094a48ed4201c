"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_mla,
                                                decode_attention_paged)
from repro.kernels.decode_attention.ref import (decode_attention_mla_ref,
                                                decode_attention_paged_ref,
                                                decode_attention_ref)
from repro.kernels.rwkv6.ops import wkv
from repro.kernels.rwkv6.ref import wkv_ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,dh,causal,off", [
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 256, 256, 4, 4, 128, True, 0),
    (2, 64, 192, 2, 1, 64, True, 128),      # chunked prefill offset
    (1, 128, 128, 8, 2, 64, False, 0),
    (1, 512, 512, 2, 1, 128, True, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Skv, H, Hkv, dh, causal, off, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, q_offset=off,
                          block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal, q_offset=off)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol(dtype),
                               rtol=tol(dtype))


@pytest.mark.parametrize("B,Smax,H,Hkv,dh,bk", [
    (2, 256, 8, 2, 64, 64),
    (3, 512, 4, 4, 128, 128),
    (2, 128, 16, 1, 64, 64),
    (1, 1024, 8, 8, 64, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Smax, H, Hkv, dh, bk, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, dh), dtype)
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, dh), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, Smax + 1)
    out = decode_attention(q, kc, vc, lengths, block_kv=bk, interpret=True)
    ref = decode_attention_ref(q.astype(jnp.float32), kc.astype(jnp.float32),
                               vc.astype(jnp.float32), lengths)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol(dtype),
                               rtol=tol(dtype))


def test_decode_attention_length_mask_exact():
    """Tokens past `length` must not leak: perturbing them changes nothing."""
    ks = jax.random.split(KEY, 4)
    B, Smax, H, Hkv, dh = 2, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (B, H, dh))
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, dh))
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, dh))
    lengths = jnp.array([40, 100])
    out1 = decode_attention(q, kc, vc, lengths, block_kv=64, interpret=True)
    kc2 = kc.at[0, 40:].set(99.0)
    vc2 = vc.at[0, 40:].set(-99.0)
    out2 = decode_attention(q, kc2, vc2, lengths, block_kv=64, interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


def _paged_case(rng, B, N, Bs, Hkv, dh, lengths, nb=None):
    """Pool [N, Bs, 2*Hkv*dh] (a row: K heads, then V heads) + per-sequence
    tables of blocks scattered through it; columns past a sequence's
    blocks (all of a length-0 one's) -> trash block 0."""
    pool = rng.standard_normal((N, Bs, 2 * Hkv * dh)).astype(np.float32)
    nb = nb or max(-(-int(l) // Bs) for l in lengths)
    tables = np.zeros((B, nb), np.int32)
    ids = iter(rng.permutation(np.arange(1, N)).tolist())
    for b, l in enumerate(lengths):
        for j in range(-(-int(l) // Bs)):
            tables[b, j] = next(ids)
    return pool, tables


# the serving groupings at block size 8: qwen2.5-3b's Hkv 2 / G 8 and
# qwen3-14b's Hkv 8 / G 5, each with a slot of length 0, one ending
# mid-block and one ending mid-chunk (chunks of 256 and of 64 or 128 keys)
PAGED_SERVING_CASES = [
    (3, 16, 2, 128, 8, 64, (0, 13, 300)),
    (3, 40, 8, 128, 8, 48, (0, 13, 200)),
]


@pytest.mark.parametrize("B,H,Hkv,dh,Bs,N,lengths", [
    (2, 8, 2, 64, 16, 32, (37, 16)),
    (3, 4, 4, 128, 32, 16, (64, 1, 90)),
    (1, 16, 1, 64, 8, 64, (100,)),
    (2, 8, 8, 64, 64, 8, (64, 128)),
] + PAGED_SERVING_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_paged(B, H, Hkv, dh, Bs, N, lengths, dtype):
    rng = np.random.default_rng(11)
    q = rng.standard_normal((B, H, dh)).astype(np.float32)
    pool, tables = _paged_case(rng, B, N, Bs, Hkv, dh, lengths)
    lens = np.asarray(lengths, np.int32)
    out = decode_attention_paged(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), jnp.asarray(tables),
        jnp.asarray(lens), interpret=True)
    ref = decode_attention_paged_ref(q, pool, tables, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=tol(dtype), rtol=tol(dtype))


# Keys a chunk holds on the K-and-V row, at block size 8 and bf16: 256 at
# qwen2.5-3b's widths (32 blocks of 8 KiB) and 128 at qwen3-14b's (16 of
# 32 KiB), as when K and V had a pool each; the two buffers hold 0.5 and
# 1 MiB of VMEM
@pytest.mark.parametrize("arch,blocks", [("qwen2.5-3b", 32),
                                         ("qwen3-14b", 16)])
def test_chunk_keys_on_the_kv_row(arch, blocks):
    from repro.configs import get_config
    from repro.kernels.decode_attention.paged_decode import chunk_blocks
    cfg = get_config(arch)
    lanes = 2 * cfg.num_kv_heads * cfg.dh
    assert chunk_blocks(8, lanes, 2, 544) == blocks
    assert 2 * blocks * 8 * lanes * 2 == {32: 1 << 19, 16: 1 << 20}[blocks]


def test_latent_chunk_keys_unchanged():
    """The latent kernel's chunks keep 25 blocks (200 keys) at
    DeepSeek-V3's 640-lane rows, block size 8, bf16."""
    from repro.configs import get_config
    from repro.kernels.decode_attention.paged_decode import (
        LATENT_CHUNK_BYTES, chunk_blocks)
    lanes = get_config("deepseek-v3").latent_lanes
    assert chunk_blocks(8, lanes, 2, 320, max_bytes=LATENT_CHUNK_BYTES) == 25


# The serving shapes (block size 8, dh 128) against the XLA decode core
# the engine runs everywhere else, over the gathered pow2 window. In f32
# the two differ only in summation order and the online rescaling, so
# 2e-5; in bf16 each p is rounded to bf16 at another scale (running max,
# not normalized), up to 2^-8 of each weight, so 2e-2.
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-14b"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_xla_decode(arch, dtype):
    from repro.configs import get_config
    from repro.kernels.decode_attention.paged_decode import chunk_blocks
    from repro.models.transformer import _decode_attend, split_kv_rows
    cfg = get_config(arch)
    H, Hkv, dh, Bs, nb = cfg.num_heads, cfg.num_kv_heads, cfg.dh, 8, 64
    chunk = Bs * chunk_blocks(Bs, 2 * Hkv * dh, jnp.dtype(dtype).itemsize,
                              nb)
    # one key, one block, one past it, a chunk, one past a chunk, the
    # whole table; the last slot is inactive: pos 0 on the trash block
    lengths = (1, 8, 9, chunk, chunk + 1, nb * Bs)
    B = len(lengths) + 1
    rng = np.random.default_rng(5)
    N = 1 + sum(-(-l // Bs) for l in lengths)
    pool, tables = _paged_case(rng, B, N, Bs, Hkv, dh, lengths + (0,), nb)
    lens = np.asarray(lengths + (1,), np.int32)
    q = jnp.asarray(rng.standard_normal((B, H, dh)), dtype)
    pj = jnp.asarray(pool, dtype)
    out = decode_attention_paged(q, pj, jnp.asarray(tables),
                                 jnp.asarray(lens), interpret=True)
    W = nb * Bs
    kd, vd = split_kv_rows(pj[tables].reshape(B, W, -1), cfg)
    valid = jnp.arange(W)[None, :] < lens[:, None]
    ref = _decode_attend(None, q[:, None], kd, vd, valid, cfg)[:, 0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("B,H,Hkv,dh,Bs,N,lengths", [
    (2, 4, 2, 64, 16, 16, (20, 33)),
] + PAGED_SERVING_CASES)
def test_decode_attention_paged_garbage_block_immunity(B, H, Hkv, dh, Bs, N,
                                                       lengths):
    """Trash-block contents and positions past `length` must not leak,
    through either half of a row: K or V."""
    rng = np.random.default_rng(13)
    lengths = np.array(lengths, np.int32)
    q = rng.standard_normal((B, H, dh)).astype(np.float32)
    # a table wider than the live blocks: its spare columns on the trash
    pool, tables = _paged_case(rng, B, N, Bs, Hkv, dh, lengths,
                               nb=-(-int(lengths.max()) // Bs) + 2)
    out1 = decode_attention_paged(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(lengths), interpret=True)
    # poison the trash block AND the tail of each sequence's last block,
    # the K half and the V half of each row
    half = Hkv * dh
    pool2 = pool.copy()
    pool2[0, :, :half] = 1e4
    pool2[0, :, half:] = -1e4
    for b, l in enumerate(lengths):
        if l == 0:
            continue
        last = tables[b, (int(l) - 1) // Bs]
        pool2[last, int(l) % Bs or Bs:, :half] = 77.0
        pool2[last, int(l) % Bs or Bs:, half:] = -77.0
    out2 = decode_attention_paged(
        jnp.asarray(q), jnp.asarray(pool2), jnp.asarray(tables),
        jnp.asarray(lengths), interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


def _latent_case(rng, B, N, Bs, lanes, used, lengths, nb=None):
    """A latent pool [N, Bs, lanes] whose lanes past ``used`` are zero (as
    the program pads its rows), with per-sequence tables as _paged_case."""
    pool = np.zeros((N, Bs, lanes), np.float32)
    pool[..., :used] = rng.standard_normal((N, Bs, used))
    _, tables = _paged_case(rng, len(lengths), N, Bs, 1, 1, lengths, nb)
    return pool, tables


# the latent kernel against its numpy twin; tolerances as the GQA kernel's:
# f32 differs in summation order only, bf16 rounds each p at another scale
@pytest.mark.parametrize("B,H,lanes,used,value,Bs,N,lengths", [
    (2, 4, 128, 40, 32, 8, 32, (37, 16)),
    (3, 16, 256, 192, 128, 16, 16, (64, 1, 90)),
    (1, 8, 128, 96, 64, 8, 64, (200,)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_mla_matches_its_twin(B, H, lanes, used, value, Bs,
                                               N, lengths, dtype):
    rng = np.random.default_rng(17)
    pool, tables = _latent_case(rng, B, N, Bs, lanes, used, lengths)
    q = np.zeros((B, H, lanes), np.float32)
    q[..., :used] = rng.standard_normal((B, H, used))
    lens = np.asarray(lengths, np.int32)
    qj, pj = jnp.asarray(q, dtype), jnp.asarray(pool, dtype)
    out = decode_attention_mla(qj, pj, jnp.asarray(tables), jnp.asarray(lens),
                               scale=0.11, value_lanes=value, interpret=True)
    ref = decode_attention_mla_ref(np.asarray(qj, np.float32),
                                   np.asarray(pj, np.float32), tables, lens,
                                   scale=0.11, value_lanes=value)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=tol(dtype), rtol=tol(dtype))


# DeepSeek-V3's published widths (128 heads, 640-lane rows of 576 used,
# 512 value lanes, block size 8) against the absorbed XLA core the engine
# runs off the TPU, over the gathered window; tolerances as above
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_kernel_matches_xla_decode(dtype):
    from repro.configs import get_config
    from repro.kernels.decode_attention.paged_decode import (
        LATENT_CHUNK_BYTES, chunk_blocks)
    from repro.models.transformer import _mla_decode_attend, _mla_scale
    cfg = get_config("deepseek-v3")
    H, lanes, Bs, nb = cfg.num_heads, cfg.latent_lanes, 8, 64
    chunk = Bs * chunk_blocks(Bs, lanes, jnp.dtype(dtype).itemsize, nb,
                              max_bytes=LATENT_CHUNK_BYTES)
    # one key, one block, one past it, a chunk, one past a chunk, the
    # whole table; the last slot is inactive: pos 0 on the trash block
    lengths = (1, 8, 9, chunk, chunk + 1, nb * Bs)
    B = len(lengths) + 1
    rng = np.random.default_rng(6)
    N = 1 + sum(-(-l // Bs) for l in lengths)
    pool, tables = _latent_case(rng, B, N, Bs, lanes, cfg.latent_dim,
                                lengths + (0,), nb)
    lens = np.asarray(lengths + (1,), np.int32)
    q = np.zeros((B, H, lanes), np.float32)
    q[..., :cfg.latent_dim] = rng.standard_normal((B, H, cfg.latent_dim))
    qj, pj = jnp.asarray(q, dtype), jnp.asarray(pool, dtype)
    out = decode_attention_mla(qj, pj, jnp.asarray(tables), jnp.asarray(lens),
                               scale=_mla_scale(cfg),
                               value_lanes=cfg.kv_lora_rank, interpret=True)
    W = nb * Bs
    valid = jnp.arange(W)[None, :] < lens[:, None]
    ref = _mla_decode_attend(qj, pj[tables].reshape(B, W, lanes), valid, cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("B,S,H,N,chunk", [
    (2, 128, 2, 64, 32),
    (1, 96, 4, 32, 32),
    (2, 64, 2, 64, 64),
    (1, 160, 2, 64, 32),     # padding path (160 % 64)
])
def test_wkv_kernel(B, S, H, N, chunk):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, N))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) - 0.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.1
    y_k, s_k = wkv(r, k, v, logw, u, s0, chunk=chunk, interpret=True)
    rr, kk, vv, lw = (a.transpose(0, 2, 1, 3).reshape(B * H, S, N)
                      for a in (r, k, v, logw))
    uu = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    y_r, s_r = wkv_ref(rr, kk, vv, lw, uu, s0.reshape(B * H, N, N))
    y_r = y_r.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    scale = max(float(jnp.max(jnp.abs(y_r))), 1.0)
    assert float(jnp.max(jnp.abs(y_k - y_r))) / scale < 1e-5
    assert float(jnp.max(jnp.abs(s_k.reshape(B * H, N, N) - s_r))) < 1e-3


def test_wkv_strong_decay_stability():
    """Strong decays must not overflow (chunked form is exp(<=0) only)."""
    B, S, H, N = 1, 128, 2, 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, N))
    logw = jnp.full((B, S, H, N), -12.0)         # near-total per-token decay
    u = jax.random.normal(ks[3], (H, N))
    s0 = jnp.zeros((B, H, N, N))
    y, s = wkv(r, k, v, logw, u, s0, chunk=32, interpret=True)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s).all())
