"""DeepSeek-V3 (latent attention, sparse experts with one shared, the
sigmoid grouped router, leading dense layers): the family interface of
``yardstick/family.py``, for one expert-parallel device's share.

Sizes: the configuration file holds the published ``config.json`` keys
as they are run (every changed key listed in ``reduced``), plus
``family``, ``arch`` (the program's registry id), ``overrides`` (fields
the program's config is changed by, as in ``dense_gqa``; ``moe`` and
``yarn`` merge into the nested configs), ``share`` (the router's full
width ``experts_routed`` and the first held expert ``expert_offset``:
``n_routed_experts`` counts the experts held here) and notes. ``dims``
reads the published keys alone and refuses any it does not cover;
``program_config`` builds the program's ``ModelConfig`` and refuses to
run if it disagrees with them.

Weights are drawn from the seed on the device, in the served type, laid
out as the program's parameter tree: leading dense layers under
``dense_blocks``, MoE layers under ``blocks``. Norm scales are drawn
away from 1; the router (float32) and its correction bias (float32, std
``BIAS_STD``) as the program keeps them.

Counts: operations and bytes at true token counts. A multiply-add is 2
operations. Each phase is counted in its least-operations form: prefill
with the latent expanded through kv_b into per-head K and V (causal: a
query at position i attends i + 1 keys, at (nope + rope) + v per head
and key), decode in the absorbed form (each head's query folded into the
latent space, 2 * H * ((kv_lora + rope) + kv_lora) per head and key, plus
the fold and unfold through kv_b). A MoE layer counts its router, its
shared expert, and the held experts' share of the routed work at its
expectation under uniform routing: top_k * held / experts_routed expert
passes per token (``expert_tokens`` of the decode spans gives the
measured count). Elementwise work (norms, rope, softmax, router choice)
is not counted. Logits are computed for the last prompt position in
prefill and for every token in decode.

The reference and its fp8 control: ``reference/deepseek_v3.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

from reference.deepseek_v3 import served_gaps  # noqa: F401  (the interface)
from yardstick.family import key_for

BF16, F32 = 2, 4
BIAS_STD = 0.1

# published keys that set a number read below
READ = {"model_type", "num_hidden_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "intermediate_size", "moe_intermediate_size",
        "first_k_dense_replace", "n_routed_experts", "num_experts_per_tok",
        "n_shared_experts", "n_group", "topk_group",
        "routed_scaling_factor", "vocab_size", "rope_theta", "rope_scaling",
        "rms_norm_eps", "tie_word_embeddings", "torch_dtype"}
# published keys that hold what this family computes only at these values
FIXED = {"hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "norm_topk_prob": True, "num_nextn_predict_layers": 0}
# published keys that change no number of the model's arithmetic here
# (the position table's length, and the modelling code's own expert
# parallelism, which the share below states)
INERT = {"max_position_embeddings", "ep_size"}
# the harness's own keys of a configuration file
HARNESS = {"family", "arch", "overrides", "share", "source", "reduced",
           "assumed", "deployment"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the reference and the operation counts need of a DeepSeek-V3
    share."""
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    expert_ff: int
    experts: int            # the router's width
    held_offset: int
    held: int               # experts computed here
    top_k: int
    shared: int
    n_group: int
    topk_group: int
    routed_scaling: float
    vocab: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    eps: float
    tied: bool

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent(self) -> int:
        """Lanes of one token's latent row: [c_kv | k_pe]."""
        return self.kv_lora + self.rope

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes of latent cache one token keeps over all layers, bf16."""
        return self.layers * self.latent * BF16

    @property
    def held_share(self) -> float:
        """Expected expert passes per token that land on a held expert."""
        return self.top_k * self.held / self.experts


def dims(c: Dict) -> Dims:
    """The sizes of configuration file ``c``, from its published keys."""
    unknown = sorted(set(c) - READ - set(FIXED) - INERT - HARNESS)
    if unknown:
        raise ValueError(f"the deepseek_v3 family does not cover the keys "
                         f"{unknown}")
    off = {k: c[k] for k, v in FIXED.items() if k in c and c[k] != v}
    if off:
        raise ValueError(f"the deepseek_v3 family covers only {FIXED}; the "
                         f"file has {off}")
    if c["model_type"] != "deepseek_v3":
        raise ValueError(f"no reference for model_type {c['model_type']!r}")
    heads = int(c["num_attention_heads"])
    if int(c["num_key_value_heads"]) != heads:
        raise ValueError("latent attention here has one K/V head per head")
    rs = c["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"the deepseek_v3 family covers YaRN rope only; "
                         f"the file has {rs}")
    share = c.get("share", {})
    held = int(c["n_routed_experts"])
    experts = int(share.get("experts_routed", held))
    held_offset = int(share.get("expert_offset", 0))
    if held_offset + held > experts:
        raise ValueError(f"held experts {held_offset}..{held_offset + held} "
                         f"exceed the router's {experts}")
    return Dims(
        layers=int(c["num_hidden_layers"]),
        dense_layers=int(c["first_k_dense_replace"]),
        d_model=int(c["hidden_size"]), heads=heads,
        q_lora=int(c["q_lora_rank"] or 0), kv_lora=int(c["kv_lora_rank"]),
        nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
        v_dim=int(c["v_head_dim"]), d_ff=int(c["intermediate_size"]),
        expert_ff=int(c["moe_intermediate_size"]), experts=experts,
        held_offset=held_offset, held=held,
        top_k=int(c["num_experts_per_tok"]),
        shared=int(c["n_shared_experts"]), n_group=int(c["n_group"]),
        topk_group=int(c["topk_group"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        vocab=int(c["vocab_size"]), rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]))


def program_config(conf: Dict, dims: Dims):
    """The program's ModelConfig for this file, checked field by field."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"])
    kw = dict(conf.get("overrides", {}))
    for nested in ("moe", "yarn"):
        if nested in kw:
            kw[nested] = dataclasses.replace(getattr(cfg, nested),
                                             **kw[nested])
    cfg = dataclasses.replace(cfg, **kw)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(dims.held_offset, dims.held)))
    m, y = cfg.moe, cfg.yarn
    got = {"num_layers": cfg.num_layers, "first_k_dense": cfg.first_k_dense,
           "d_model": cfg.d_model, "num_heads": cfg.num_heads,
           "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
           "qk_nope_dim": cfg.qk_nope_dim, "qk_rope_dim": cfg.qk_rope_dim,
           "v_head_dim": cfg.v_head_dim, "d_ff": cfg.d_ff,
           "d_ff_expert": m.d_ff_expert, "num_experts": m.num_experts,
           "held": m.held, "top_k": m.top_k,
           "num_shared_experts": m.num_shared_experts, "router": m.router,
           "n_group": m.n_group, "topk_group": m.topk_group,
           "routed_scaling": m.routed_scaling, "vocab_size": cfg.vocab_size,
           "padded_vocab": cfg.padded_vocab, "padded_heads": cfg.padded_heads,
           "rope_theta": cfg.rope_theta,
           "yarn": (y.factor, y.original_max_position, y.beta_fast,
                    y.beta_slow, y.mscale, y.mscale_all_dim),
           "norm_eps": cfg.norm_eps, "tie_embeddings": cfg.tie_embeddings,
           "dtype": cfg.dtype, "block": cfg.block,
           "sliding_window": cfg.sliding_window, "kv_quant": cfg.kv_quant}
    d = dims
    want = {"num_layers": d.layers, "first_k_dense": d.dense_layers,
            "d_model": d.d_model, "num_heads": d.heads,
            "q_lora_rank": d.q_lora, "kv_lora_rank": d.kv_lora,
            "qk_nope_dim": d.nope, "qk_rope_dim": d.rope,
            "v_head_dim": d.v_dim, "d_ff": d.d_ff,
            "d_ff_expert": d.expert_ff, "num_experts": d.experts,
            "held": (d.held_offset, d.held), "top_k": d.top_k,
            "num_shared_experts": d.shared, "router": "sigmoid",
            "n_group": d.n_group, "topk_group": d.topk_group,
            "routed_scaling": d.routed_scaling, "vocab_size": d.vocab,
            "padded_vocab": d.vocab, "padded_heads": d.heads,
            "rope_theta": d.rope_theta,
            "yarn": (d.yarn_factor, d.yarn_original, d.yarn_beta_fast,
                     d.yarn_beta_slow, d.yarn_mscale, d.yarn_mscale_all_dim),
            "norm_eps": d.eps, "tie_embeddings": d.tied,
            "dtype": conf["torch_dtype"], "block": "attn",
            "sliding_window": 0, "kv_quant": False}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config {conf['arch']} differs from the "
                         f"configuration file (program, file): {bad}")
    return cfg


def make_params(dims: Dims, seed: int, dtype: str = "bfloat16"):
    """The weight tree for ``dims``, from ``seed``, on the default device."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    d = dims
    D, H, R, L = d.d_model, d.heads, d.kv_lora, d.layers
    qk = d.nope + d.rope

    def drawer(key):
        keys = iter(jax.random.split(key, 24))

        def normal(shape, std, as_=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(as_)

        def scale(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dt)
        return normal, scale

    def attention(normal, scale):
        p = {"attn_norm": scale((D,)),
             "wkv_a": normal((D, d.latent), 1 / math.sqrt(D)),
             "kv_a_norm": scale((R,)),
             "wkv_b": normal((R, H, d.nope + d.v_dim), 1 / math.sqrt(R)),
             "wo": normal((H, d.v_dim, D), 1 / math.sqrt(H * d.v_dim * L)),
             "ffn_norm": scale((D,))}
        if d.q_lora:
            p["wq_a"] = normal((D, d.q_lora), 1 / math.sqrt(D))
            p["q_a_norm"] = scale((d.q_lora,))
            p["wq_b"] = normal((d.q_lora, H, qk), 1 / math.sqrt(d.q_lora))
        else:
            p["wq"] = normal((D, H, qk), 1 / math.sqrt(D))
        return p

    def dense_layer(key):
        # one layer at a time (lax.map), so no float32 copy of a stacked
        # weight is ever held on the device
        normal, scale = drawer(key)
        p = attention(normal, scale)
        p.update(wi_gate=normal((D, d.d_ff), 1 / math.sqrt(D)),
                 wi_up=normal((D, d.d_ff), 1 / math.sqrt(D)),
                 wo_ffn=normal((d.d_ff, D), 1 / math.sqrt(d.d_ff * L)))
        return p

    def moe_layer(key):
        normal, scale = drawer(key)
        p = attention(normal, scale)
        E, F, S = d.held, d.expert_ff, d.expert_ff * d.shared
        p["moe"] = {
            "router": normal((D, d.experts), 1 / math.sqrt(D), jnp.float32),
            "router_bias": normal((d.experts,), BIAS_STD, jnp.float32),
            "wg": normal((E, D, F), 1 / math.sqrt(D)),
            "wu": normal((E, D, F), 1 / math.sqrt(D)),
            "wd": normal((E, F, D), 1 / math.sqrt(F * L)),
            "shared_wg": normal((D, S), 1 / math.sqrt(D)),
            "shared_wu": normal((D, S), 1 / math.sqrt(D)),
            "shared_wd": normal((S, D), 1 / math.sqrt(S * L))}
        return p

    def build(key):
        k_dense, k_moe, k_embed, k_head, k_norm = jax.random.split(key, 5)
        p = {"embed": drawer(k_embed)[0]((d.vocab, D), 1.0),
             "blocks": jax.lax.map(moe_layer,
                                   jax.random.split(k_moe, d.moe_layers)),
             "final_norm": drawer(k_norm)[1]((D,)),
             "lm_head": drawer(k_head)[0]((D, d.vocab), 1 / math.sqrt(D))}
        if d.dense_layers:
            p["dense_blocks"] = jax.lax.map(
                dense_layer, jax.random.split(k_dense, d.dense_layers))
        return p

    return jax.jit(build)(key_for(seed))


# ---- counts ---------------------------------------------------------------

def attn_proj_params(d: Dims) -> int:
    """Weights of one layer's attention projections, kv_b included."""
    qk = d.nope + d.rope
    q = (d.d_model * d.q_lora + d.q_lora * d.heads * qk if d.q_lora
         else d.d_model * d.heads * qk)
    return (q + d.d_model * d.latent + d.kv_lora * d.heads * (d.nope + d.v_dim)
            + d.heads * d.v_dim * d.d_model)


def attn_layer_params(d: Dims) -> int:
    """Every weight of one layer's attention and its norms."""
    return (attn_proj_params(d) + d.d_model + d.kv_lora
            + (d.q_lora or 0) + d.d_model)


def expert_params(d: Dims) -> int:
    """One routed expert's (or one shared expert's) weights."""
    return 3 * d.d_model * d.expert_ff


def moe_always_params(d: Dims) -> int:
    """What every MoE layer reads each step whatever the routing: the
    shared experts, in bf16, and the router and its bias, in float32, as
    bf16-sized units (x2 for float32)."""
    return d.shared * expert_params(d) + 2 * (d.d_model + 1) * d.experts


def ffn_flops_per_token(d: Dims, moe: bool) -> float:
    if not moe:
        return 2 * 3 * d.d_model * d.d_ff
    return (2 * d.d_model * d.experts
            + 2 * expert_params(d) * (d.shared + d.held_share))


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def prefill_flops(d: Dims, isl: int) -> float:
    """One prompt of ``isl`` tokens, first token's logits included: the
    latent expanded into per-head K and V."""
    pairs = isl * (isl + 1) // 2
    attn = (2 * isl * attn_proj_params(d)
            + 2 * d.heads * (d.nope + d.rope + d.v_dim) * pairs)
    return (d.layers * attn
            + isl * (d.dense_layers * ffn_flops_per_token(d, False)
                     + d.moe_layers * ffn_flops_per_token(d, True))
            + head_flops(d))


def mla_decode_attn_flops(d: Dims, live: int) -> int:
    """The absorbed latent attention core over ``live`` keys summed over
    a step's tokens: scores over whole rows, values over the c_kv lanes,
    every head, every layer."""
    return d.layers * 2 * d.heads * (d.latent + d.kv_lora) * live


def mla_decode_attn_bytes(d: Dims, live: int) -> int:
    """Latent rows the core reads: each live key's row, every layer."""
    return d.kv_bytes_per_token * live


def decode_flops(d: Dims, context: int) -> float:
    """One decoded token that attends to ``context`` positions (itself
    included), in the absorbed form."""
    qk = d.nope + d.rope
    q = (d.d_model * d.q_lora + d.q_lora * d.heads * qk if d.q_lora
         else d.d_model * d.heads * qk)
    per_layer = 2 * (q + d.d_model * d.latent
                     + d.heads * d.nope * d.kv_lora     # fold into latent
                     + d.heads * d.kv_lora * d.v_dim    # unfold
                     + d.heads * d.v_dim * d.d_model)
    return (d.layers * per_layer + mla_decode_attn_flops(d, context)
            + d.dense_layers * ffn_flops_per_token(d, False)
            + d.moe_layers * ffn_flops_per_token(d, True) + head_flops(d))


def held_touched(d: Dims, batch: int) -> float:
    """Held experts that expect at least one of ``batch`` tokens."""
    return d.held * (1 - (1 - d.top_k / d.experts) ** batch)


def decode_step_bytes(d: Dims, contexts: Iterable[int]) -> float:
    """Least bytes one decode step moves: every layer's attention and
    dense weights, each MoE layer's router, bias, shared expert and the
    held experts it expects to touch, the output head, the live latent
    rows of every request at its true length, each request's embedding
    row, and each new token's latent row written back."""
    contexts = list(contexts)
    B = len(contexts)
    weights = (d.layers * attn_layer_params(d)
               + d.dense_layers * (3 * d.d_model * d.d_ff + d.d_model)
               + d.moe_layers * (moe_always_params(d) + d.d_model
                                 + held_touched(d, B) * expert_params(d))
               + d.d_model * d.vocab + d.d_model) * BF16
    kv = d.kv_bytes_per_token
    return weights + sum(contexts) * kv + B * (d.d_model * BF16 + kv)


def moe_decode_flops(d: Dims, expert_tokens: int, layer_tokens: int) -> int:
    """Operations of the MoE layers of decode steps: ``expert_tokens``
    held-expert passes, and per token of each MoE layer
    (``layer_tokens``) the router and the shared experts."""
    return (2 * expert_params(d) * expert_tokens
            + layer_tokens * (2 * d.d_model * d.experts
                              + 2 * expert_params(d) * d.shared))


def moe_decode_bytes(d: Dims, experts_touched: int, steps: int) -> int:
    """Least weight bytes of the MoE layers of ``steps`` decode steps:
    each touched held expert once, and per step every MoE layer's shared
    experts, router and bias."""
    return (experts_touched * expert_params(d)
            + steps * d.moe_layers * moe_always_params(d)) * BF16
