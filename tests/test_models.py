"""Model substrate behaviour: decode==full-forward, chunked prefill, padding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig, MoEConfig, YarnConfig
from repro.models import transformer as T
from repro.models import layers as L

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            d_ff=128, vocab_size=97, remat=False, logits_chunk=16,
            dtype="float32")

FAMILIES = {
    "dense": ModelConfig(name="dense", family="dense", **TINY),
    "bias+qknorm": ModelConfig(name="b", family="dense", qkv_bias=True,
                               qk_norm=True, **TINY),
    "moe": ModelConfig(name="moe", family="moe",
                       moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                                     num_shared_experts=1,
                                     capacity_factor=4.0), **TINY),
    "rwkv": ModelConfig(name="rwkv", family="ssm", block="rwkv", **TINY),
    "hybrid": ModelConfig(name="hy", family="hybrid", block="hybrid",
                          sliding_window=8, ssm_state=4, **TINY),
    # latent attention (expanded in prefill, absorbed in decode), YaRN,
    # a dense layer then a held-expert MoE layer with the grouped router
    "mla+moe": ModelConfig(
        name="mla", family="moe", q_lora_rank=16, kv_lora_rank=32,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, first_k_dense=1,
        yarn=YarnConfig(factor=40.0, original_max_position=4096),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                      num_shared_experts=1, router="sigmoid", n_group=4,
                      topk_group=2, routed_scaling=2.5, held=(2, 4)),
        **TINY),
}

KEY = jax.random.PRNGKey(1)


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_decode_matches_full_forward(fam):
    cfg = FAMILIES[fam]
    params = T.init_params(cfg, KEY)
    B, S = 2, 13
    toks = jax.random.randint(KEY, (B, S + 2), 0, cfg.vocab_size)
    lg_ref, _ = T.prefill_full(params, cfg, {"tokens": toks[:, :S + 1]})
    _, cache = T.prefill_full(params, cfg, {"tokens": toks[:, :S]},
                              capacity=S + 8)
    lg_step, cache = T.decode_step(params, cfg, cache, toks[:, S])
    np.testing.assert_allclose(lg_step, lg_ref, atol=3e-4)
    lg_ref2, _ = T.prefill_full(params, cfg, {"tokens": toks[:, :S + 2]})
    lg_step2, _ = T.decode_step(params, cfg, cache, toks[:, S + 1])
    np.testing.assert_allclose(lg_step2, lg_ref2, atol=3e-4)


def test_chunked_prefill_matches_full():
    cfg = FAMILIES["dense"]
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    lg_f, c_f = T.prefill_full(params, cfg, {"tokens": toks})
    lg_c, c_c = T.prefill_chunked(params, cfg, {"tokens": toks}, 4)
    np.testing.assert_allclose(lg_f, lg_c, atol=3e-4)
    np.testing.assert_allclose(c_f["k"], c_c["k"], atol=3e-4)


def test_padded_heads_exact_semantics():
    """pad_heads_to must not change outputs (padded heads are masked)."""
    base = FAMILIES["dense"]
    padded = base.replace(pad_heads_to=3)     # 4 heads -> 6 (pad 2)
    assert padded.padded_heads == 6
    params_p = T.init_params(padded, KEY)
    # build unpadded params by slicing the padded q/o projections
    params_u = jax.tree.map(lambda x: x, params_p)
    params_u["blocks"] = dict(params_p["blocks"])
    params_u["blocks"]["wq"] = params_p["blocks"]["wq"][:, :, :4]
    params_u["blocks"]["wo"] = params_p["blocks"]["wo"][:, :4]
    toks = jax.random.randint(KEY, (2, 12), 0, base.vocab_size)
    lg_p, _ = T.prefill_full(params_p, padded, {"tokens": toks})
    lg_u, _ = T.prefill_full(params_u, base, {"tokens": toks})
    np.testing.assert_allclose(lg_p, lg_u, atol=3e-4)


def test_padded_vocab_never_wins():
    cfg = FAMILIES["dense"].replace(vocab_pad=31)       # 97 -> 128
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    lg, cache = T.prefill_full(params, cfg, {"tokens": toks})
    assert lg.shape[-1] == 128
    assert int(jnp.argmax(lg, -1).max()) < 97
    assert float(lg[:, 97:].max()) <= L.NEG_INF * 0.5
    loss, _ = T.train_loss(params, cfg, {"tokens": toks, "labels": toks})
    assert jnp.isfinite(loss)


def test_sliding_window_attention_matches_dense():
    key = jax.random.PRNGKey(3)
    B, S, H, dh, W = 2, 24, 2, 16, 8
    q = jax.random.normal(key, (B, S, H, dh))
    out_w = L.sliding_window_attention_xla(q, q, q, W)
    out_d = L.dense_attention(q, q, q, causal=True, window=W)
    np.testing.assert_allclose(out_w, out_d, atol=2e-5)


def test_causal_flash_xla_matches_dense():
    key = jax.random.PRNGKey(4)
    B, S, H, dh = 2, 64, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))
    out_f = L.causal_attention_xla(q, k, v, block=16)
    out_d = L.dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out_f, out_d, atol=2e-5)


def test_train_loss_grads_finite_all_families():
    for fam, cfg in FAMILIES.items():
        params = T.init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        (loss, _), g = jax.value_and_grad(
            lambda p: T.train_loss(p, cfg, batch), has_aux=True)(params)
        assert jnp.isfinite(loss), fam
        gn = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                 for x in jax.tree.leaves(g))
        assert jnp.isfinite(gn), fam


def test_kv_quant_decode_close_to_fp():
    """int8 KV decode: bounded quantization error vs bf path."""
    cfg = FAMILIES["dense"].replace(kv_quant=True)
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 14), 0, cfg.vocab_size)
    lg_ref, _ = T.prefill_full(params, cfg.replace(kv_quant=False),
                               {"tokens": toks})
    _, cache = T.prefill_full(params, cfg, {"tokens": toks[:, :13]},
                              capacity=20)
    assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
    lg, _ = T.decode_step(params, cfg, cache, toks[:, 13])
    assert float(jnp.max(jnp.abs(lg - lg_ref))) < 0.08


def test_grouped_vs_expand_decode_identical():
    """grouped_decode is a pure layout change: bit-comparable outputs."""
    base = FAMILIES["dense"]
    params = T.init_params(base, KEY)
    toks = jax.random.randint(KEY, (2, 12), 0, base.vocab_size)
    _, cache = T.prefill_full(params, base, {"tokens": toks[:, :11]},
                              capacity=16)
    lg_g, _ = T.decode_step(params, base, cache, toks[:, 11])
    lg_e, _ = T.decode_step(params, base.replace(grouped_decode=False),
                            cache, toks[:, 11])
    np.testing.assert_allclose(lg_g, lg_e, atol=2e-5)


def test_rwkv_block_pallas_matches_xla():
    from repro.models import rwkv6
    cfg = FAMILIES["rwkv"]
    p = rwkv6.init_rwkv_block(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    st = rwkv6.init_rwkv_state(cfg, 2)
    y1, s1 = rwkv6.rwkv_block(p, x, st, cfg, impl="xla")
    y2, s2 = rwkv6.rwkv_block(p, x, st, cfg, impl="pallas", interpret=True)
    np.testing.assert_allclose(y1, y2, atol=1e-4)
    np.testing.assert_allclose(s1["s"], s2["s"], atol=1e-3)
