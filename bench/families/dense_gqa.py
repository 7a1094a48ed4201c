"""Dense GQA decoders (Qwen2: QKV bias; Qwen3: RMS norm of each q and k
head): the family interface of ``yardstick/family.py``.

Sizes: the configuration file holds the model's published
``config.json`` keys as they are run (every changed key listed in
``reduced``), plus ``family``, ``arch`` (the program's registry id),
``overrides`` (fields the program's config is changed by to match) and
notes. ``dims`` reads the published keys alone and refuses any it does
not cover; ``program_config`` builds the program's ``ModelConfig`` and
refuses to run if it disagrees with them.

Weights: the benchmark, not the program, makes them, so the program
cannot choose what it is checked on, and the reference reads the same
numbers. They are made in one jitted call on the device, in the type
they are served in, and laid out as the program's parameter tree expects
(``Rig`` checks the tree against the program's own abstract one). Norm
scales and QKV biases are drawn away from their trivial values (1 and
0), so that a path that skipped them would show in the logits.

Counts: operations and bytes at true token counts, never at the
program's padded shapes, so the same work reads the same whatever
implements it. A multiply-add is 2 operations. Attention is causal: a
query at position i attends to i + 1 keys. Elementwise work (norms,
RoPE, softmax, biases) is not counted. Logits are computed for the last
prompt position in prefill and for every token in decode.

The reference and its fp8 control: ``reference/dense.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

from reference.dense import served_gaps  # noqa: F401  (the interface)
from yardstick.family import key_for

BF16 = 2

# published keys that set a number read below
READ = {"model_type", "num_hidden_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "vocab_size", "rope_theta", "rms_norm_eps",
        "attention_bias", "tie_word_embeddings", "torch_dtype"}
# published keys that hold what this family computes only at these values
FIXED = {"hidden_act": "silu", "use_sliding_window": False,
         "rope_scaling": None, "attention_dropout": 0.0}
# published keys that change no number of the model's arithmetic here
# (token ids, initialisation, cache use, limits of the position table,
# and the window that ``use_sliding_window: false`` leaves unused)
INERT = {"architectures", "bos_token_id", "eos_token_id",
         "initializer_range", "max_position_embeddings", "max_window_layers",
         "sliding_window", "use_cache", "transformers_version"}
# the harness's own keys of a configuration file
HARNESS = {"family", "arch", "overrides", "source", "reduced", "assumed",
           "deployment"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the reference and the operation counts need of a dense GQA
    decoder."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    qk_norm: bool
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes of K and V one token keeps over all layers, in bf16."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2


def dims(c: Dict) -> Dims:
    """The sizes of configuration file ``c``, from its published keys."""
    unknown = sorted(set(c) - READ - set(FIXED) - INERT - HARNESS)
    if unknown:
        raise ValueError(f"the dense_gqa family does not cover the keys "
                         f"{unknown}")
    off = {k: c[k] for k, v in FIXED.items() if k in c and c[k] != v}
    if off:
        raise ValueError(f"the dense_gqa family covers only {FIXED}; the "
                         f"file has {off}")
    kind = c["model_type"]
    if kind not in ("qwen2", "qwen3"):
        raise ValueError(f"no reference for model_type {kind!r}")
    heads = int(c["num_attention_heads"])
    return Dims(
        layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        heads=heads,
        kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
        d_ff=int(c["intermediate_size"]),
        vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]),
        qkv_bias=kind == "qwen2" or bool(c.get("attention_bias")),
        qk_norm=kind == "qwen3",
        tied=bool(c["tie_word_embeddings"]))


def program_config(conf: Dict, dims: Dims):
    """The program's ModelConfig for this file, checked field by field."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"])
    cfg = dataclasses.replace(cfg, **conf.get("overrides", {}))
    want = {"num_layers": dims.layers, "d_model": dims.d_model,
            "num_heads": dims.heads, "num_kv_heads": dims.kv_heads,
            "dh": dims.head_dim, "d_ff": dims.d_ff,
            "vocab_size": dims.vocab, "padded_vocab": dims.vocab,
            "padded_heads": dims.heads, "rope_theta": dims.rope_theta,
            "norm_eps": dims.eps, "qkv_bias": dims.qkv_bias,
            "qk_norm": dims.qk_norm, "tie_embeddings": dims.tied,
            "dtype": conf["torch_dtype"], "block": "attn", "moe": None,
            "sliding_window": 0, "kv_quant": False}
    got = {k: getattr(cfg, k) for k in want}
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
    L, D, H, K, dh, F, V = (dims.layers, dims.d_model, dims.heads,
                            dims.kv_heads, dims.head_dim, dims.d_ff,
                            dims.vocab)

    def drawer(key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dt)

        def scale(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dt)
        return normal, scale

    def layer(key):
        # one layer at a time (lax.map), so no float32 copy of a stacked
        # weight is ever held on the device
        normal, scale = drawer(key)
        p: Dict[str, object] = {
            "attn_norm": scale((D,)),
            "wq": normal((D, H, dh), 1 / math.sqrt(D)),
            "wk": normal((D, K, dh), 1 / math.sqrt(D)),
            "wv": normal((D, K, dh), 1 / math.sqrt(D)),
            "wo": normal((H, dh, D), 1 / math.sqrt(H * dh * L)),
            "ffn_norm": scale((D,)),
            "wi_gate": normal((D, F), 1 / math.sqrt(D)),
            "wi_up": normal((D, F), 1 / math.sqrt(D)),
            "wo_ffn": normal((F, D), 1 / math.sqrt(F * L)),
        }
        if dims.qkv_bias:
            p["bq"] = normal((H, dh), 0.5)
            p["bk"] = normal((K, dh), 0.5)
            p["bv"] = normal((K, dh), 0.5)
        if dims.qk_norm:
            p["q_norm"] = scale((dh,))
            p["k_norm"] = scale((dh,))
        return p

    def by_rows(key, shape, std, rows=128):
        # a [V, D]-sized matrix drawn in blocks of rows, for the same reason
        if shape[0] % rows:
            return drawer(key)[0](shape, std)
        blocks = jax.lax.map(lambda k: drawer(k)[0]((rows,) + shape[1:], std),
                             jax.random.split(key, shape[0] // rows))
        return blocks.reshape(shape)

    def build(key):
        k_layers, k_embed, k_head, k_norm = jax.random.split(key, 4)
        return {"embed": by_rows(k_embed, (V, D), 1.0),
                "blocks": jax.lax.map(layer, jax.random.split(k_layers, L)),
                "final_norm": drawer(k_norm)[1]((D,)),
                "lm_head": by_rows(k_head, (D, V), 1 / math.sqrt(D))}

    return jax.jit(build)(key_for(seed))


def layer_matmul_params(d: Dims) -> int:
    """Weights of one layer's matrix products (q, k, v, o and the MLP)."""
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def layer_params(d: Dims) -> int:
    """Every weight of one layer: products, norms, biases."""
    n = layer_matmul_params(d) + 2 * d.d_model
    if d.qkv_bias:
        n += d.head_dim * (d.heads + 2 * d.kv_heads)
    if d.qk_norm:
        n += 2 * d.head_dim
    return n


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def prefill_flops(d: Dims, isl: int) -> int:
    """One prompt of ``isl`` tokens, first token's logits included."""
    pairs = isl * (isl + 1) // 2
    per_layer = (2 * isl * layer_matmul_params(d)
                 + 4 * d.heads * d.head_dim * pairs)
    return d.layers * per_layer + head_flops(d)


def decode_flops(d: Dims, context: int) -> int:
    """One decoded token that attends to ``context`` positions (itself
    included)."""
    per_layer = 2 * layer_matmul_params(d) + 4 * d.heads * d.head_dim * context
    return d.layers * per_layer + head_flops(d)


def weight_bytes_per_step(d: Dims) -> int:
    """Weights a decode step reads: every layer and the output head (the
    embedding is read only at the batch's rows, counted per token)."""
    return (d.layers * layer_params(d) + d.d_model * d.vocab
            + d.d_model) * BF16


def decode_step_bytes(d: Dims, contexts: Iterable[int]) -> int:
    """Least bytes one decode step moves: its weights, the live KV of
    every request at its true length, each request's embedding row, and
    each new token's K and V written back."""
    contexts = list(contexts)
    kv = d.kv_bytes_per_token
    return (weight_bytes_per_step(d) + sum(contexts) * kv
            + len(contexts) * (d.d_model * BF16 + kv))
