"""Seeded random weights for a dense GQA decoder, made by the benchmark.

The benchmark, not the program, makes the weights, so the program cannot
choose what it is checked on, and the reference reads the same numbers.
They are made in one jitted call on the device, in the type they are
served in, and laid out as the program's parameter tree expects
(``run.py`` checks the tree against the program's own abstract one).

Norm scales and QKV biases are drawn away from their trivial values
(1 and 0), so that a path that skipped them would show in the logits.
"""
from __future__ import annotations

import math
from typing import Dict

from yardstick.model import Dims


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


def key_for(seed: int):
    """A jax PRNG key for a seed of any size."""
    import jax
    key = jax.random.PRNGKey(seed % (1 << 32))
    return jax.random.fold_in(key, (seed >> 32) % (1 << 31))
