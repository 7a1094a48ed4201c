"""Expert-parallel MoE FFN (the paper's "TEP": TP attention + EP FFN).

Dispatch is capacity-based but *sort-free and one-hot-free on the hot path*:
instead of materializing a [T, E, C] dispatch tensor (infeasible at 384
experts), we build a tiny [E, C] slot->token index via cumsum + scatter and
move activations with gathers. Inside ``shard_map`` every model-shard:

  1. routes all local tokens (router compute is tiny and replicated),
  2. gathers the rows for *its* E/ep experts into [E_local, C, D],
  3. runs the expert SwiGLU as one batched einsum (MXU-friendly),
  4. scatter-adds gated outputs into a partial [T, D] and ``psum``s over
     the model axis — the same collective volume as a dense TP FFN.

Tokens stay sharded over (pod, data); experts live on the model axis. No
all-to-all is needed because activations are model-replicated at the FFN
boundary (standard Megatron TP residual stream).

A layer told which experts it holds (``MoEConfig.held``: first expert and
count, e.g. chip 0 of DeepSeek-V3's EP32 holds experts 0-7) takes
``held_moe`` instead: it routes every token over all experts and
computes, dropless, the part of the routed sum its own experts give. On
one device that part is the layer's result; the exchange that would add
the other devices' parts is not run here.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import MoEConfig
from repro.parallel.sharding import current_mesh, current_rules

f32 = jnp.float32


def expert_capacity(num_tokens: int, cfg: MoEConfig) -> int:
    c = math.ceil(cfg.capacity_factor * num_tokens * cfg.top_k
                  / cfg.num_experts)
    return int(min(num_tokens, max(c, cfg.min_capacity)))


def _sigmoid_topk(logits, bias, cfg: MoEConfig):
    """DeepSeek-V3's ``noaux_tc`` choice: sigmoid scores s; the choice
    reads s + bias; a group scores the sum of its best two choices, the
    best ``topk_group`` groups stay and the others are masked to -inf (as
    vLLM's ``grouped_topk``; HF masks to 0); the top-k of what is left.
    Weights are s there, normalised to sum 1, times ``routed_scaling``.
    Returns (weights [T,k] f32, expert ids [T,k])."""
    T, E = logits.shape
    s = jax.nn.sigmoid(logits)
    choice = s + bias.astype(f32)[None, :]
    G = cfg.n_group
    group = jnp.sum(jax.lax.top_k(choice.reshape(T, G, E // G), 2)[0], -1)
    _, keep = jax.lax.top_k(group, cfg.topk_group)              # [T,g]
    kept = jnp.sum(jax.nn.one_hot(keep, G, dtype=jnp.int32), axis=1) > 0
    choice = jnp.where(jnp.repeat(kept, E // G, axis=1), choice, -jnp.inf)
    _, ids = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling, ids


def _route(x, router_w, cfg: MoEConfig, bias=None):
    """Returns (gates [T,E] dense fp32, mask [T,E] int32, aux metrics)."""
    logits = jnp.einsum("td,de->te", x.astype(f32), router_w.astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.router == "sigmoid":
        top_p, top_ids = _sigmoid_topk(logits, bias, cfg)
    else:
        top_p, top_ids = jax.lax.top_k(probs, cfg.top_k)        # [T,k]
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renorm
    mask = jnp.sum(jax.nn.one_hot(top_ids, cfg.num_experts, dtype=jnp.int32),
                   axis=1)                                      # [T,E]
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_ids].set(top_p)
    # Load-balancing aux loss (Switch-style) + router z-loss. Under
    # shard_map these are computed from *per-data-shard* statistics and
    # pmean'd — a deliberate choice: at scale, per-device balance is what
    # controls dispatch skew, and it avoids an extra collective.
    frac_tokens = jnp.mean(mask.astype(f32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.num_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gates, mask, aux, z


def held_moe(x, params, cfg: MoEConfig):
    """x: [T, D]. The routed-expert sum over this device's held experts
    (``cfg.held``), dropless: every held expert runs over every token, a
    token off its top-k with a zero gate, so no token is ever dropped
    and the shape does not depend on the routing. Returns (y [T, D] f32,
    hits [T, E_held] bool: which held experts each token was routed
    to)."""
    off, n = cfg.held
    gates, mask, _, _ = _route(x, params["router"], cfg,
                               params.get("router_bias"))
    g = gates[:, off:off + n]                                    # [T,n]
    h = jnp.einsum("td,edf->etf", x, params["wg"])
    u = jnp.einsum("td,edf->etf", x, params["wu"])
    h = jax.nn.silu(h.astype(f32)).astype(x.dtype) * u
    ye = jnp.einsum("etf,efd->etd", h, params["wd"],
                    preferred_element_type=f32)
    y = jnp.einsum("etd,te->td", ye, g)
    return y, mask[:, off:off + n] > 0


def _local_moe(x, router_w, wg, wu, wd, *, cfg: MoEConfig,
               ep_axis: Optional[str], dp_axes: Tuple[str, ...],
               combine_fp32: bool = True, tp_axes: Tuple[str, ...] = (),
               router_bias=None):
    """x: [T, D] local tokens; wg/wu/wd: [E_local, D, H(/tp)] expert shards.

    tp_axes non-empty = expert-TP serving mode: the expert hidden dim is
    sharded over those axes too (weights fully resident, no FSDP gather);
    the combine psum then spans (ep + tp) axes."""
    T, D = x.shape
    E = cfg.num_experts
    E_local = wg.shape[0]
    C = expert_capacity(T, cfg)

    gates, mask, aux_loss, z_loss = _route(x, router_w, cfg, router_bias)

    # position of token t in expert e's buffer (cumsum over tokens)
    pos = jnp.cumsum(mask, axis=0) - 1                           # [T,E]
    keep = (mask == 1) & (pos < C)
    dropped = (jnp.sum(mask) - jnp.sum(keep)).astype(f32)

    # slot -> token index table, sentinel T = padded zero row
    tok_ids = jnp.broadcast_to(jnp.arange(T)[:, None], (T, E))
    e_ids = jnp.broadcast_to(jnp.arange(E)[None, :], (T, E))
    safe_pos = jnp.where(keep, pos, C)                           # C = drop slot
    slot_tok = jnp.full((E, C + 1), T, jnp.int32)
    slot_tok = slot_tok.at[e_ids.reshape(-1), safe_pos.reshape(-1)].set(
        jnp.where(keep, tok_ids, T).reshape(-1), mode="drop")
    slot_tok = slot_tok[:, :C]                                   # [E,C]

    # slice this shard's experts
    if ep_axis is not None:
        e_off = jax.lax.axis_index(ep_axis) * E_local
    else:
        e_off = 0
    slot_tok_l = jax.lax.dynamic_slice_in_dim(slot_tok, e_off, E_local, 0)

    # gather expert inputs  [E_local, C, D]
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    xe = x_pad[slot_tok_l]

    # expert SwiGLU as batched einsum
    h = jnp.einsum("ecd,edh->ech", xe, wg)
    u = jnp.einsum("ecd,edh->ech", xe, wu)
    h = jax.nn.silu(h.astype(f32)).astype(x.dtype) * u
    ye = jnp.einsum("ech,ehd->ecd", h, wd)                       # [E_local,C,D]

    # per-slot gate value: gates[token, expert]
    gates_pad = jnp.concatenate([gates, jnp.zeros((1, E), f32)], axis=0)
    local_e = e_off + jnp.arange(E_local)
    slot_gate = gates_pad[slot_tok_l, local_e[:, None]]          # [E_local,C]

    # scatter-add combine -> partial sum over local experts
    y = jnp.zeros((T + 1, D), f32)
    y = y.at[slot_tok_l.reshape(-1)].add(
        (ye.astype(f32) * slot_gate[..., None]).reshape(-1, D))
    y = y[:T]
    if not combine_fp32:
        y = y.astype(x.dtype)
    if ep_axis is not None:
        axes = (ep_axis,) + tuple(tp_axes)
        y = jax.lax.psum(y, axes if len(axes) > 1 else ep_axis)
    # reduce aux metrics to replicated scalars
    if dp_axes:
        aux_loss = jax.lax.pmean(aux_loss, dp_axes)
        z_loss = jax.lax.pmean(z_loss, dp_axes)
        dropped = jax.lax.psum(dropped, dp_axes)
    aux = {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss,
           "moe_dropped": dropped}
    return y.astype(x.dtype), aux


def moe_ffn(x, params, cfg: MoEConfig, *, combine_fp32: bool = True,
            expert_tp: bool = False) -> Tuple[jnp.ndarray, dict]:
    """x: [B, S, D]; params: router [D,E], wg/wu/wd [E,D,H] (+ shared_*).

    expert_tp: serving mode for small-token batches — tokens replicated
    over the mesh, expert d_ff sharded over the data axes (EP x TP expert
    weights fully resident; combine psums over both axes). Kills the
    per-step FSDP weight all-gather that otherwise dominates giant-MoE
    decode (EXPERIMENTS.md §Perf, kimi-k2 iteration 1)."""
    B, S, D = x.shape
    mesh = current_mesh()
    rules = current_rules()
    ep_size = (math.prod(mesh.shape[a] for a in rules.ep)
               if mesh is not None and rules.ep else 1)
    dp_size = (math.prod(mesh.shape[a] for a in rules.dp)
               if mesh is not None and rules.dp else 1)
    dff = params["wg"].shape[-1]
    use_expert_tp = (expert_tp and mesh is not None and ep_size > 1
                     and cfg.num_experts % ep_size == 0
                     and dp_size > 1 and dff % dp_size == 0)
    use_shard_map = (mesh is not None and ep_size > 1
                     and cfg.num_experts % ep_size == 0
                     and (B * S) % dp_size == 0 and B % dp_size == 0)
    xf = x.reshape(B * S, D)

    if cfg.held is not None:
        with jax.named_scope("moe"):
            y, hits = held_moe(xf, params, cfg)
            y = y.astype(x.dtype).reshape(B, S, D)
            if cfg.num_shared_experts:
                from repro.models.layers import swiglu
                y = y + swiglu(x, params["shared_wg"], params["shared_wu"],
                               params["shared_wd"])
        return y, {"held_hits": hits}
    if use_expert_tp:
        fn = partial(_local_moe, cfg=cfg, ep_axis=rules.ep[0], dp_axes=(),
                     combine_fp32=combine_fp32, tp_axes=rules.dp)
        mapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, None), P(None, None),
                      P(rules.ep, None, rules.dp),
                      P(rules.ep, None, rules.dp),
                      P(rules.ep, rules.dp, None)),
            out_specs=(P(None, None),
                       {"moe_aux_loss": P(), "moe_z_loss": P(),
                        "moe_dropped": P()}),
            check_vma=False,
        )
        y, aux = mapped(xf, params["router"], params["wg"], params["wu"],
                        params["wd"])
    elif use_shard_map:
        fn = partial(_local_moe, cfg=cfg, ep_axis=rules.ep[0],
                     dp_axes=rules.dp, combine_fp32=combine_fp32)
        mapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(rules.dp, None), P(None, None),
                      P(rules.ep, None, None), P(rules.ep, None, None),
                      P(rules.ep, None, None)),
            out_specs=(P(rules.dp, None),
                       {"moe_aux_loss": P(), "moe_z_loss": P(),
                        "moe_dropped": P()}),
            check_vma=False,
        )
        y, aux = mapped(xf, params["router"], params["wg"], params["wu"],
                        params["wd"])
    else:
        y, aux = _local_moe(xf, params["router"], params["wg"], params["wu"],
                            params["wd"], cfg=cfg, ep_axis=None, dp_axes=(),
                            combine_fp32=combine_fp32,
                            router_bias=params.get("router_bias"))

    y = y.reshape(B, S, D)

    if cfg.num_shared_experts:
        from repro.models.layers import swiglu
        y = y + swiglu(x, params["shared_wg"], params["shared_wu"],
                       params["shared_wd"])
    return y, aux
