"""A configuration file's sizes, read the way the published config says.

``bench/configs/<name>.json`` holds the model's published ``config.json``
keys as they are run (with every changed key listed in ``reduced``), plus
``arch`` (the program's registry id), ``overrides`` (fields the program's
config is changed by to match) and notes. ``Dims`` is read from the
published keys alone; ``program_config`` builds the program's
``ModelConfig`` and refuses to run if it disagrees with them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the reference and the operation counts need of a dense GQA
    decoder (Qwen2: QKV bias; Qwen3: RMS norm of each q and k head)."""
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

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        kind = c["model_type"]
        if kind not in ("qwen2", "qwen3"):
            raise ValueError(f"no reference for model_type {kind!r}")
        heads = int(c["num_attention_heads"])
        return cls(
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

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes of K and V one token keeps over all layers, in bf16."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2


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
