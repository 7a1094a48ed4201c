"""Ahead-of-time compiles for one chip of a described TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described topology from shapes alone. So a BlockSpec the
Mosaic lowering refuses, or a full-width serving step that does not fit
the chip's HBM, fails here instead of on the chip. Nothing runs: these
tests say nothing about results or times.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_mla,
                                                decode_attention_paged)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.ops import wkv
from repro.models import transformer as T

BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
QWEN = get_config("qwen2.5-3b")
RWKV = get_config("rwkv6-1.6b")
H, HKV, DH = QWEN.num_heads, QWEN.num_kv_heads, QWEN.dh
# the benchmark's DeepSeek-V3 share: 7 layers (3 dense, 4 MoE), experts
# 0-7 of 256 held, an eighth of the vocabulary
_DSV3 = get_config("deepseek-v3")
DSV3 = dataclasses.replace(_DSV3, num_layers=7, vocab_size=16160,
                           moe=dataclasses.replace(_DSV3.moe, held=(0, 8)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"     # no compiler logs on disk

    def restore_env():
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        restore_env()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache: keep them out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    restore_env()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


# kernel -> (call, argument specs) at the widths the registry gives
# qwen2.5-3b (attention, bf16) and rwkv6-1.6b (WKV heads, f32)
KERNELS = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 512, H, DH), BF), ((1, 512, HKV, DH), BF),
         ((1, 512, HKV, DH), BF)]),
    "decode_attention": (
        decode_attention,
        [((8, H, DH), BF), ((8, 2048, HKV, DH), BF),
         ((8, 2048, HKV, DH), BF), ((8,), I32)]),
    # the serving engine's default block_size (8) and one bf16 tile (16),
    # over the pool's [N, Bs, 2*Hkv*dh] layout (a row: K heads, V heads)
    "paged_decode_bs8": (
        decode_attention_paged,
        [((8, H, DH), BF), ((2049, 8, 2 * HKV * DH), BF), ((8, 256), I32),
         ((8,), I32)]),
    "paged_decode_bs16": (
        decode_attention_paged,
        [((8, H, DH), BF), ((1025, 16, 2 * HKV * DH), BF), ((8, 128), I32),
         ((8,), I32)]),
    # DeepSeek-V3's latent rows: 128 heads, 640 lanes (576 used), 512
    # value lanes, block size 8
    "paged_latent_decode": (
        lambda q, pool, tbl, lens: decode_attention_mla(
            q, pool, tbl, lens, scale=0.1, value_lanes=DSV3.kv_lora_rank),
        [((8, DSV3.num_heads, DSV3.latent_lanes), BF),
         ((2049, 8, DSV3.latent_lanes), BF), ((8, 256), I32), ((8,), I32)]),
    "wkv": (
        wkv,
        [((1, 256, RWKV.num_heads, RWKV.dh), F32)] * 4
        + [((RWKV.num_heads, RWKV.dh), F32),
           ((1, RWKV.num_heads, RWKV.dh, RWKV.dh), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen_params(one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        T.abstract_params(QWEN))


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_full_width_decode_step_paged_fits_v5e(one_chip, qwen_params):
    """The engine's paged decode step: 8 slots of 2048 tokens over the
    pool the engine sizes for them (block_size 8)."""
    slots, capacity, bs = 8, 2048, 8
    nb = capacity // bs
    blocks = 1 + QWEN.num_layers * nb * (slots + 4)
    (kv,) = _shapes(one_chip, ((blocks, bs, 2 * QWEN.padded_kv_heads * DH),
                               BF))
    pool = {"kv": kv}
    tables, pos, tokens = _shapes(
        one_chip, ((QWEN.num_layers, slots, nb), I32), ((slots,), I32),
        ((slots,), I32))
    step = jax.jit(lambda p, pool, tbl, pos, t: T.decode_step_paged(
        p, QWEN, pool, tbl, pos, t), donate_argnums=(1,))
    compiled = step.lower(qwen_params, pool, tables, pos, tokens).compile()
    assert _device_bytes(compiled) <= TPU_V5E.hbm_cap


# (config, slots, table blocks of the engine, table blocks of the step):
# cell 1's decode engine (16 slots, capacity 4352) at its widest window,
# and one 10-layer stage of 40/8 heads (32 slots, capacity 2560) at 2048
KERNEL_STEPS = {
    "qwen2.5-3b": (QWEN, 16, 544, 544),
    "qwen3-14b-l10": (dataclasses.replace(get_config("qwen3-14b"),
                                          num_layers=10), 32, 320, 256),
}


@pytest.mark.parametrize("name", sorted(KERNEL_STEPS))
def test_full_width_decode_step_on_kernel_fits_v5e(one_chip, name):
    """The paged decode step on the Pallas kernel reads the pool where it
    lies: it fits HBM, and its temporaries stay under a quarter of the
    pool's K half, where any copy, reshape or transpose of the pool would
    need a whole one (the kernel's first version relaid the pools out)."""
    cfg, slots, nb_max, nb = KERNEL_STEPS[name]
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        T.abstract_params(cfg))
    blocks = 1 + cfg.num_layers * nb_max * (slots + 4)
    half = cfg.padded_kv_heads * cfg.dh
    (kv,) = _shapes(one_chip, ((blocks, 8, 2 * half), BF))
    pool = {"kv": kv}
    tables, pos, tokens = _shapes(
        one_chip, ((cfg.num_layers, slots, nb), I32), ((slots,), I32),
        ((slots,), I32))
    step = jax.jit(lambda p, pool, tbl, pos, t: T.decode_step_paged(
        p, cfg, pool, tbl, pos, t, impl="pallas"), donate_argnums=(1,))
    compiled = step.lower(params, pool, tables, pos, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= TPU_V5E.hbm_cap
    half_bytes = blocks * 8 * half * 2
    assert compiled.memory_analysis().temp_size_in_bytes < half_bytes / 4


@pytest.mark.parametrize("step", ["prefill_chunked_paged", "scatter_blocks"])
def test_pool_writes_stay_in_place_on_v5e(one_chip, qwen_params, step):
    """The colocated engine's other programs on the pool (32 slots,
    capacity 2560, block size 8): a chunked prefill of a 256-token prompt
    in chunks of 128, and the insert of a 1024-token payload. Both write
    the donated pool in place: their temporaries stay under a quarter of
    the pool's K half, where a copy or relayout of the pool would need
    the whole of it."""
    L, half = QWEN.num_layers, QWEN.padded_kv_heads * DH
    blocks = 1 + L * 320 * (32 + 4)
    (kv,) = _shapes(one_chip, ((blocks, 8, 2 * half), BF))
    pool = {"kv": kv}
    if step == "prefill_chunked_paged":
        tokens, tbl = _shapes(one_chip, ((1, 256), I32), ((L, 32), I32))
        fn = jax.jit(lambda p, t, pool, tbl: T.prefill_chunked_paged(
            p, QWEN, {"tokens": t}, 128, pool, tbl), donate_argnums=(2,))
        lowered = fn.lower(qwen_params, tokens, pool, tbl)
    else:
        (ids,) = _shapes(one_chip, ((L, 128), I32))
        payload = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            jax.eval_shape(T.gather_blocks, pool, ids))
        fn = jax.jit(T.scatter_blocks, donate_argnums=(0,))
        lowered = fn.lower(pool, ids, payload)
    compiled = lowered.compile()
    half_bytes = blocks * 8 * half * 2
    assert compiled.memory_analysis().temp_size_in_bytes < half_bytes / 4


def test_full_width_prefill_full_fits_v5e(one_chip, qwen_params):
    """The prefill engine's step on one 2048-token prompt."""
    (tokens,) = _shapes(one_chip, ((1, 2048), I32))
    step = jax.jit(lambda p, t: T.prefill_full(p, QWEN, {"tokens": t}))
    compiled = step.lower(qwen_params, tokens).compile()
    assert _device_bytes(compiled) <= TPU_V5E.hbm_cap


@pytest.fixture(scope="module")
def dsv3_params(one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        T.abstract_params(DSV3))


def _dsv3_pool(one_chip, slots=128, capacity=2560, bs=8):
    """The latent pool the engine sizes for the cell's 128 slots."""
    nb = capacity // bs
    blocks = 1 + DSV3.num_layers * nb * (slots + 4)
    (kv,) = _shapes(one_chip, ((blocks, bs, DSV3.latent_lanes), BF))
    return {"kv": kv}, nb, blocks * bs * DSV3.latent_lanes * 2


def test_deepseek_v3_decode_step_on_kernel_fits_v5e(one_chip, dsv3_params):
    """The DeepSeek-V3 share's decode step at 128 slots on the latent
    kernel: it fits HBM beside the weights and the pool, and reads the
    pool in place (temporaries under a quarter of it)."""
    slots = 128
    pool, nb, pool_bytes = _dsv3_pool(one_chip, slots)
    tables, pos, tokens = _shapes(
        one_chip, ((DSV3.num_layers, slots, nb), I32), ((slots,), I32),
        ((slots,), I32))
    step = jax.jit(lambda p, pool, tbl, pos, t: T.decode_step_paged(
        p, DSV3, pool, tbl, pos, t, impl="pallas"), donate_argnums=(1,))
    compiled = step.lower(dsv3_params, pool, tables, pos, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= TPU_V5E.hbm_cap
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


def test_deepseek_v3_chunked_prefill_fits_v5e(one_chip, dsv3_params):
    """The share's chunk-128 prefill of the cell's longest prompt (1024
    tokens, 8 chunks) into the same pool."""
    pool, _, _ = _dsv3_pool(one_chip)
    tokens, tbl = _shapes(one_chip, ((1, 1024), I32),
                          ((DSV3.num_layers, 1024 // 8), I32))
    step = jax.jit(lambda p, t, pool, tbl: T.prefill_chunked_paged(
        p, DSV3, {"tokens": t}, 128, pool, tbl), donate_argnums=(2,))
    compiled = step.lower(dsv3_params, tokens, pool, tbl).compile()
    assert _device_bytes(compiled) <= TPU_V5E.hbm_cap
