"""Per-assigned-architecture smoke tests (REQUIRED, see assignment).

Each instantiates the REDUCED config of the same family and runs one
forward/train step + one prefill/decode on CPU, asserting output shapes and
no NaNs. Full configs are exercised only via the dry-run.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import transformer as T
from repro.models.frontends import synth_inputs
from repro.models.config import ShapeConfig

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, KEY)
    shape = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")
    batch = synth_inputs(cfg, shape)
    loss, metrics = jax.jit(lambda p, b: T.train_loss(p, cfg, b))(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), (arch, loss)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, KEY)
    shape = ShapeConfig("smoke", seq_len=24, global_batch=2, kind="prefill")
    inputs = synth_inputs(cfg, shape)
    logits, cache = jax.jit(
        lambda p, i: T.prefill_full(p, cfg, i, capacity=32))(params, inputs)
    assert logits.shape == (2, cfg.padded_vocab)
    assert jnp.isfinite(logits.astype(jnp.float32)).all(), arch
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = jax.jit(
        lambda p, c, t: T.decode_step(p, cfg, c, t))(params, cache, nxt)
    assert logits2.shape == (2, cfg.padded_vocab)
    assert jnp.isfinite(logits2.astype(jnp.float32)).all(), arch
    assert (cache2["pos"] == cache["pos"] + 1).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL config fields must match the assigned table exactly."""
    spec = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "deepseek-v3": (61, 7168, 128, 128, 18432, 129280),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == spec, (arch, got, spec)
    if arch == "kimi-k2-1t-a32b":
        assert cfg.moe.num_experts == 384 and cfg.moe.top_k == 8
    if arch == "granite-moe-1b-a400m":
        assert cfg.moe.num_experts == 32 and cfg.moe.top_k == 8
    if arch == "hymba-1.5b":
        assert cfg.ssm_state == 16 and cfg.block == "hybrid"
    if arch == "qwen2.5-3b":
        assert cfg.qkv_bias
    if arch == "qwen3-14b":
        assert cfg.qk_norm
    if arch == "rwkv6-1.6b":
        assert cfg.block == "rwkv" and cfg.is_attention_free
    if arch == "deepseek-v3":
        assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
                cfg.qk_rope_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
        assert cfg.first_k_dense == 3 and cfg.yarn.factor == 40
        assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
                cfg.moe.num_shared_experts) == (256, 8, 2048, 1)
        assert (cfg.moe.router, cfg.moe.n_group, cfg.moe.topk_group,
                cfg.moe.routed_scaling) == ("sigmoid", 8, 4, 2.5)
        # MLA keeps 576 latent lanes a token per layer
        assert cfg.kv_bytes_per_token() == 61 * 576 * 2
