"""Unified model configuration covering every assigned architecture.

One ``ModelConfig`` drives a single decoder implementation with optional
blocks (GQA attention, MoE FFN, RWKV6 recurrence, Mamba SSM hybrid) so that
all ten assigned architectures — dense / MoE / SSM / hybrid / audio / VLM —
are instances of the same substrate.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    # Minimum per-expert capacity (slots); guards tiny decode batches against
    # routing skew. Effective capacity = min(T, max(cf*T*k/E, min_capacity)).
    min_capacity: int = 8
    router_jitter: float = 0.0
    # --- router: "softmax" (renormalised top-k of the softmax), or
    # "sigmoid" (DeepSeek-V3's noaux_tc: sigmoid scores, a per-expert
    # correction bias that steers the choice only, the top ``topk_group``
    # of ``n_group`` groups by the sum of each group's best two, weights
    # normalised and scaled by ``routed_scaling``) ---
    router: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    # --- experts held by this device, as (first expert, count): the layer
    # routes over all ``num_experts`` and computes, dropless, the part of
    # the result its own experts give (an expert-parallel share without
    # the exchange). None: every expert, on the capacity path ---
    held: Optional[Tuple[int, int]] = None

    @property
    def held_count(self) -> int:
        return self.held[1] if self.held else self.num_experts


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V3's ``rope_scaling``, type "yarn")."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    # --- attention options ---
    qkv_bias: bool = False         # qwen2.5
    qk_norm: bool = False          # qwen3
    rope_theta: float = 10_000.0
    sliding_window: int = 0        # 0 = full causal; >0 = SWA (hymba)
    yarn: Optional[YarnConfig] = None
    # --- latent attention (MLA, DeepSeek-V2/V3): on when kv_lora_rank > 0.
    # q through a rank-q_lora_rank bottleneck (0: one projection); K/V
    # through a cached latent of kv_lora_rank plus one shared rope key of
    # qk_rope_dim; heads of qk_nope_dim + qk_rope_dim (q, k) and v_head_dim
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # --- block composition ---
    block: str = "attn"            # attn | rwkv | hybrid
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 0         # leading layers with a dense FFN (d_ff)
    # --- SSM (hybrid / mamba branch) ---
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 1
    # --- frontend stubs ---
    frontend: Optional[str] = None  # None | "audio" | "vision"
    vision_patches: int = 0         # llava: number of anyres patch embeddings
    vision_dim: int = 1024
    # --- numerics ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    logits_chunk: int = 1024        # seq-chunked CE to bound logits memory
    # --- sharding-driven padding (semantics-exact, masked; DESIGN.md §5) ---
    pad_heads_to: int = 0           # pad q-head count to this multiple
    vocab_pad: int = 0              # extra (masked) vocab rows for sharding
    # --- serving-perf knobs (EXPERIMENTS.md §Perf levers) ---
    kv_quant: bool = False          # int8 KV cache w/ per-token-head scales
    moe_combine_fp32: bool = True   # MoE combine psum precision
    moe_expert_tp: bool = False     # shard expert d_ff over the data axis
    #     (weight-resident MoE decode: no per-step FSDP all-gather)
    grouped_decode: bool = True     # GQA decode w/o materializing expanded KV
    # --- training ---
    optimizer: str = "adamw"        # adamw | adafactor (factored, for >=100B)
    remat: bool = True
    grad_accum: int = 1             # microbatch accumulation steps
    tie_embeddings: bool = False

    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Lanes of one token's latent cache row: [c_kv | k_pe]."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes of a latent cache row as stored: ``latent_dim`` rounded up
        to whole 128-lane tiles (a TPU lays a 576-lane row out as 640 in
        HBM anyway, and its DMAs move whole tiles); the lanes past
        ``latent_dim`` hold zeros."""
        return -(-self.latent_dim // 128) * 128

    @property
    def dense_layers(self) -> int:
        """Leading layers with a dense FFN before the MoE layers."""
        return min(self.first_k_dense, self.num_layers) if self.moe else 0

    def yarn_mscale(self) -> float:
        """YaRN's attention temperature 0.1 * mscale_all_dim * ln(factor)
        + 1 (1 without YaRN): the softmax scale is multiplied by its
        square."""
        y = self.yarn
        if y is None or y.factor <= 1 or not y.mscale_all_dim:
            return 1.0
        return 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0

    @property
    def q_group(self) -> int:
        return self.num_heads // self.num_kv_heads if self.num_kv_heads else 0

    @property
    def padded_heads(self) -> int:
        """q-head count padded to a shardable multiple; padded heads are
        masked out of the output projection (exact semantics, wasted FLOPs
        charged in the roofline)."""
        if not self.pad_heads_to:
            return self.num_heads
        import math as _m
        return _m.ceil(self.num_heads / self.pad_heads_to) * self.pad_heads_to

    @property
    def padded_vocab(self) -> int:
        return self.vocab_size + self.vocab_pad

    @property
    def padded_kv_heads(self) -> int:
        """KV heads padded so padded q heads group evenly (enables the
        grouped decode-attention path). Only grows when Hp % q_group == 0."""
        Hp = self.padded_heads
        g = self.q_group
        if Hp != self.num_heads and g and Hp % g == 0:
            return Hp // g
        return self.num_kv_heads

    @property
    def can_group_decode(self) -> bool:
        g = self.q_group
        return (self.block in ("attn", "hybrid") and g > 0
                and self.padded_heads % g == 0
                and self.padded_heads // g == self.padded_kv_heads)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_attention_free(self) -> bool:
        return self.block == "rwkv"

    @property
    def sub_quadratic(self) -> bool:
        """True if decode state is O(window) / O(1) per token (long_500k ok)."""
        return self.block in ("rwkv", "hybrid")

    def param_count(self) -> int:
        """Total parameter count (analytic, matches init)."""
        D, dh, L = self.d_model, self.dh, self.num_layers
        n = self.vocab_size * D                              # embed
        if not self.tie_embeddings:
            n += D * self.vocab_size                         # lm_head
        n += D                                               # final norm
        per_layer = 0
        if self.mla:
            H, R = self.num_heads, self.kv_lora_rank
            qk = self.qk_nope_dim + self.qk_rope_dim
            if self.q_lora_rank:
                per_layer += (D + 1) * self.q_lora_rank          # q_a, norm
                per_layer += self.q_lora_rank * H * qk           # q_b
            else:
                per_layer += D * H * qk
            per_layer += D * self.latent_dim + R                 # kv_a, norm
            per_layer += R * H * (self.qk_nope_dim + self.v_head_dim)
            per_layer += H * self.v_head_dim * D + D             # wo, norm
        elif self.block in ("attn", "hybrid"):
            per_layer += D * self.num_heads * dh             # wq
            per_layer += 2 * D * self.num_kv_heads * dh      # wk, wv
            per_layer += self.num_heads * dh * D             # wo
            if self.qkv_bias:
                per_layer += (self.num_heads + 2 * self.num_kv_heads) * dh
            if self.qk_norm:
                per_layer += 2 * dh
            per_layer += D                                   # attn norm
        if self.block == "hybrid":
            di = self.ssm_expand * D
            per_layer += D * 2 * di                          # in_proj (x, z)
            per_layer += di * self.ssm_conv                  # conv
            per_layer += di * (2 * self.ssm_state + 1)       # B, C, dt proj
            per_layer += di * 2                              # A_log, D skip
            per_layer += di * D                              # out_proj
        if self.block == "rwkv":
            # time-mix: r,k,v,g,o + decay lora + u; channel-mix: rk, kv, vk
            per_layer += 5 * D * D + 2 * D * 64 + 64 * D + 2 * D
            per_layer += D * int(3.5 * D) * 2 + D * D        # channel mix
            per_layer += 2 * D                               # two norms
        dense_ffn = 3 * D * self.d_ff + D                    # swiglu, norm
        if self.moe is not None:
            m = self.moe
            moe_ffn = D * m.num_experts                      # router
            if m.router == "sigmoid":
                moe_ffn += m.num_experts                     # its bias
            moe_ffn += m.held_count * 3 * D * m.d_ff_expert
            moe_ffn += m.num_shared_experts * 3 * D * m.d_ff_expert
            moe_ffn += D                                     # ffn norm
            k = self.dense_layers
            return n + L * per_layer + k * dense_ffn + (L - k) * moe_ffn
        if self.block != "rwkv":
            per_layer += dense_ffn
        return n + L * per_layer

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        total = self.param_count()
        moe_layers = self.num_layers - self.dense_layers
        all_expert = (moe_layers * m.held_count * 3 * self.d_model
                      * m.d_ff_expert)
        active_expert = moe_layers * (m.top_k + m.num_shared_experts) * (
            3 * self.d_model * m.d_ff_expert)
        return total - all_expert + active_expert

    def kv_bytes_per_token(self, bytes_element: int = 2) -> int:
        """KV-cache bytes per token (for Eq 1/2 transfer analysis)."""
        if self.block == "rwkv":
            return 0  # O(1) state, not per-token
        if self.mla:
            return self.num_layers * self.latent_dim * bytes_element
        per_layer = 2 * self.num_kv_heads * self.dh * bytes_element
        return self.num_layers * per_layer

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
