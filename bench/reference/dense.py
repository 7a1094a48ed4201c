"""Plain float32 forward pass of a dense GQA decoder (Qwen2 / Qwen3).

Written from the published model description, independent of the
program: token embedding; per layer an RMS norm, q/k/v projections (with
bias in Qwen2), an RMS norm over each q and k head (Qwen3), rotary
position embedding on the two halves of each head (``rotate_half``, base
``rope_theta``), causal attention with each group of ``heads / kv_heads``
query heads sharing one K/V head, the output projection, a residual add,
then an RMS norm and a SwiGLU MLP with its residual add; a final RMS norm
and the output head. No cache, no batching, no kernels. Every product
runs in float32 under ``jax.default_matmul_precision("highest")``.

It runs one sequence at a time, layer by layer, so that it fits beside
the weights: the sequence is padded at its end to a multiple of
``BUCKET`` tokens (causal attention keeps the padding from reaching any
real position) and attention runs over blocks of ``QBLOCK`` queries.

``quant="fp8"`` is the control: the same pass with the inputs of every
product rounded to float8 e4m3 (per-row scales for activations, per
output column for weights), the precision step below bfloat16.

``dims`` is the ``Dims`` of the ``dense_gqa`` family
(``bench/families/dense_gqa.py``), whose ``served_gaps`` this is.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


f32 = jnp.float32
BUCKET = 1024
QBLOCK = 512
HEAD_ROWS = 256
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s


def _mm(a, w, quant):
    """a [..., K] @ w [K, N] in float32, or with fp8-rounded inputs."""
    if quant == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, h, dh]: rotate the (first half, second half) pairs."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=f32) / dh))
    ang = pos[:, None].astype(f32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, blocks, i, dims, quant: Optional[str]):
    p = jax.tree.map(lambda a: a[i].astype(f32), blocks)
    S, D = x.shape
    H, K, dh = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    h = _rms(x, p["attn_norm"], dims.eps)
    q = _mm(h, p["wq"].reshape(D, H * dh), quant).reshape(S, H, dh)
    k = _mm(h, p["wk"].reshape(D, K * dh), quant).reshape(S, K, dh)
    v = _mm(h, p["wv"].reshape(D, K * dh), quant).reshape(S, K, dh)
    if dims.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if dims.qk_norm:
        q = _rms(q, p["q_norm"], dims.eps)
        k = _rms(k, p["k_norm"], dims.eps)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    g = H // K
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)   # [S, H, dh]
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)

    def attend(qb_lo):
        qb, lo = qb_lo
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(dh)
        qpos = lo + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            pr = _fp8(pr, -1)
        return jnp.einsum("hqk,khd->qhd", pr, v)

    nq = S // QBLOCK
    o = jax.lax.map(attend, (q.reshape(nq, QBLOCK, H, dh),
                             jnp.arange(nq) * QBLOCK))
    o = o.reshape(S, H * dh)
    x = x + _mm(o, p["wo"].reshape(H * dh, D), quant)
    h = _rms(x, p["ffn_norm"], dims.eps)
    gate = _mm(h, p["wi_gate"], quant)
    up = _mm(h, p["wi_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, p["wo_ffn"], quant)


@jax.jit
def _embed_rows(embed, tokens):
    return embed[tokens].astype(f32)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _head(x, lo, final_norm, head, targets, dims, quant):
    """HEAD_ROWS rows of hidden states from row ``lo`` -> (best logit,
    logit of target, argmax) per row."""
    h = jax.lax.dynamic_slice_in_dim(x, lo, HEAD_ROWS)
    h = _rms(h, final_norm.astype(f32), dims.eps)
    logits = _mm(h, head.astype(f32), quant)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def hidden(params, dims, tokens: np.ndarray, quant=None):
    """Hidden states before the final norm, float32, one row per token and
    at least HEAD_ROWS rows of padding after them."""
    n = len(tokens)
    S = -(-(n + HEAD_ROWS) // BUCKET) * BUCKET
    toks = np.zeros((S,), np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(params["embed"], jnp.asarray(toks))
        for i in range(dims.layers):
            x = _layer(x, params["blocks"], i, dims, quant)
    return x


def head_stats(params, dims, x, row0: int, targets, quant=None):
    """For the rows of x from ``row0``, one per target: (best logit, logit
    of the target, argmax), each a numpy array, over blocks of rows."""
    head = params["embed"].T if dims.tied else params["lm_head"]
    n = len(targets)
    out = [], [], []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, HEAD_ROWS):
            m = min(HEAD_ROWS, n - lo)
            t = np.zeros((HEAD_ROWS,), np.int32)
            t[:m] = targets[lo:lo + m]
            res = _head(x, np.int32(row0 + lo), params["final_norm"], head,
                        jnp.asarray(t), dims, quant)
            for acc, r in zip(out, res):
                acc.append(np.asarray(r)[:m])
    return tuple(np.concatenate(a) for a in out)


def served_gaps(params, dims, prompt, output, *, control=False):
    """Teacher-forced over prompt + served tokens: at each served token,
    the reference's best logit minus its logit for the served token, and
    with ``control`` also minus its logit for the token the fp8 pass puts
    first there. Returns (served gaps, control gaps or None)."""
    prompt = np.asarray(prompt, np.int32)
    output = np.asarray(output, np.int32)
    seq = np.concatenate([prompt, output[:-1]])
    row0 = len(prompt) - 1
    x = hidden(params, dims, seq)
    best, at, _ = head_stats(params, dims, x, row0, output)
    if not control:
        return best - at, None
    xq = hidden(params, dims, seq, quant="fp8")
    _, _, pick = head_stats(params, dims, xq, row0, output, quant="fp8")
    del xq
    _, at_pick, _ = head_stats(params, dims, x, row0, pick)
    return best - at, best - at_pick
