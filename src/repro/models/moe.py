"""Mixture-of-Experts block.

Two implementations, same math:

- ``moe_block_global``: capacity-based dispatch in pure global-view jnp.
  Used un-meshed (CPU smoke tests / tiny models).  GSPMD materialises
  [k*T, D] slot tensors for this formulation, so it is NOT used on the
  production mesh (measured: 48 GiB/device buffers for granite train_4k).

- ``moe_block_ep``: production path.  shard_map over (data, model): tokens
  stay on their data shard, experts live on model shards; dispatch into a
  local [E, C_loc, D] buffer, all_to_all over the model axis to the expert
  owners, batched expert matmuls, reverse all_to_all, local combine.  This
  is the GShard/Switch EP flow; collective bytes = 2 round-trips of the
  capacity buffer per layer, FLOPs ~ capacity_factor x active.

Experts whose count does not divide the model axis (granite: 40 on 16) are
padded to the next multiple (48); phantom experts receive zero capacity
weight and ~20% FLOP overhead, recorded in the roofline notes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import mlp_block

P = jax.sharding.PartitionSpec


@dataclasses.dataclass(frozen=True)
class EPSpec:
    """Expert-parallel execution context (mesh + axis names)."""
    mesh: Any
    data_axes: Tuple[str, ...]
    model_axis: str = "model"
    capacity_factor: float = 1.25

    @property
    def dp(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.model_axis]


def moe_capacity(num_tokens: int, moe: MoEConfig, capacity_factor: float = 1.25,
                 num_buckets: Optional[int] = None) -> int:
    e = num_buckets or moe.num_experts
    cap = int(num_tokens * moe.top_k * capacity_factor / e)
    return max(4, -(-cap // 4) * 4)


def _route(xt, router, k):
    """Returns (topw [T,k] f32, topi [T,k] i32, gates [T,E] f32)."""
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi, gates


def _dispatch_local(xt, topi, topw, e_pad: int, cap: int):
    """Local capacity dispatch.  xt: [T,D]; topi/topw: [T,k].

    Returns buf [e_pad, cap, D], and (slot_e, pos, keep, slot_t) for combine.
    """
    t, d = xt.shape
    k = topi.shape[1]
    slot_e = topi.T.reshape(-1)                   # [k*T] rank-major priority
    slot_t = jnp.tile(jnp.arange(t), k)
    onehot = jax.nn.one_hot(slot_e, e_pad, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos_all, slot_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    pos = jnp.where(keep, pos, cap - 1)
    upd = jnp.where(keep[:, None], xt[slot_t], 0)
    buf = jnp.zeros((e_pad, cap, d), xt.dtype).at[slot_e, pos].add(upd, mode="drop")
    return buf, (slot_e, pos, keep, slot_t)


def _combine_local(out_buf, routing, topw, t: int, d: int, dtype):
    slot_e, pos, keep, slot_t = routing
    k = topw.shape[1]
    slot_gate = topw.T.reshape(-1)
    slot_out = out_buf[slot_e, pos] * (slot_gate * keep)[:, None].astype(dtype)
    return jnp.zeros((t, d), dtype).at[slot_t].add(slot_out)


def _expert_mlps(buf, wg, wu, wd, variant):
    h_gate = jnp.einsum("ecd,edf->ecf", buf, wg)
    h_up = jnp.einsum("ecd,edf->ecf", buf, wu)
    act = (jax.nn.silu(h_gate) if variant == "swiglu"
           else jax.nn.gelu(h_gate, approximate=True))
    return jnp.einsum("ecf,efd->ecd", act * h_up, wd)


def _aux_loss(gates, topi, e):
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    frac_gates = jnp.mean(gates, axis=0)
    return e * jnp.sum(frac_tokens * frac_gates)


# --------------------------------------------------------------------------
# global-view path (un-meshed smoke tests)
# --------------------------------------------------------------------------

def moe_block_global(x, p, moe: MoEConfig, mlp_variant: str, *,
                     capacity_factor: float = 1.25,
                     constrain=lambda t, spec: t):
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    topw, topi, gates = _route(xt, p["router"], moe.top_k)
    cap = moe_capacity(t, moe, capacity_factor)
    buf, routing = _dispatch_local(xt, topi, topw, moe.num_experts, cap)
    out_buf = _expert_mlps(buf, p["w_gate"], p["w_up"], p["w_down"], mlp_variant)
    y = _combine_local(out_buf, routing, topw, t, d, x.dtype)
    if moe.shared_expert_ff:
        y = y + mlp_block(xt, p["shared"], mlp_variant)
    return y.reshape(b, s, d), _aux_loss(gates, topi, moe.num_experts)


# --------------------------------------------------------------------------
# expert-parallel shard_map path (production mesh)
# --------------------------------------------------------------------------

def moe_block_ep(x, p, moe: MoEConfig, mlp_variant: str, ep: EPSpec, *,
                 constrain=lambda t, spec: t):
    b, s, d = x.shape
    t = b * s
    e, k = moe.num_experts, moe.top_k
    tp = ep.tp
    e_pad = -(-e // tp) * tp
    # Shard tokens over (data x model) jointly when possible: with tokens
    # only data-sharded, every model rank would dispatch the SAME tokens and
    # the all_to_all would deliver tp identical copies to each expert —
    # correct but tp-x duplicated compute (measured 16x on granite).
    token_axes = (ep.data_axes + (ep.model_axis,)
                  if t % (ep.dp * tp) == 0 else ep.data_axes)
    shards = ep.dp * tp if t % (ep.dp * tp) == 0 else ep.dp
    t_loc = t // shards
    cap = moe_capacity(t_loc, moe, ep.capacity_factor, num_buckets=e_pad)

    xt = x.reshape(t, d)
    xt = constrain(xt, "moe_tokens")      # align tokens to the EP layout
                                          # BEFORE shard_map (kills GSPMD's
                                          # "involuntary full remat" path)
    topw, topi, gates = _route(xt, p["router"], k)

    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if e_pad != e:
        padn = e_pad - e
        wg = jnp.concatenate([wg, jnp.zeros((padn,) + wg.shape[1:], wg.dtype)], 0)
        wu = jnp.concatenate([wu, jnp.zeros((padn,) + wu.shape[1:], wu.dtype)], 0)
        wd = jnp.concatenate([wd, jnp.zeros((padn,) + wd.shape[1:], wd.dtype)], 0)

    db = ep.data_axes
    ma = ep.model_axis

    def local_fn(xt_l, topw_l, topi_l, wg_l, wu_l, wd_l):
        # xt_l: [T_loc, D]; w*_l: [E_loc, D, F]
        buf, routing = _dispatch_local(xt_l, topi_l, topw_l, e_pad, cap)
        # to expert owners: [E_pad, C, D] -> [E_loc, tp*C, D]
        buf = jax.lax.all_to_all(buf, ma, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = _expert_mlps(buf, wg_l, wu_l, wd_l, mlp_variant)
        # back to token owners: [E_loc, tp*C, D] -> [E_pad, C, D]
        out = jax.lax.all_to_all(out, ma, split_axis=1, concat_axis=0,
                                 tiled=True)
        return _combine_local(out, routing, topw_l, xt_l.shape[0], d, xt_l.dtype)

    y = jax.shard_map(
        local_fn, mesh=ep.mesh,
        in_specs=(P(token_axes, None), P(token_axes, None),
                  P(token_axes, None),
                  P(ma, None, None), P(ma, None, None), P(ma, None, None)),
        out_specs=P(token_axes, None), check_vma=False,
    )(xt, topw, topi, wg, wu, wd)

    if moe.shared_expert_ff:
        y = y + mlp_block(xt, p["shared"], mlp_variant)
    return y.reshape(b, s, d), _aux_loss(gates, topi, e)


def moe_block(x, p, moe: MoEConfig, mlp_variant: str, *,
              capacity_factor: float = 1.25,
              constrain=lambda t, spec: t, ep: Optional[EPSpec] = None):
    if ep is not None:
        return moe_block_ep(x, p, moe, mlp_variant, ep, constrain=constrain)
    return moe_block_global(x, p, moe, mlp_variant,
                            capacity_factor=capacity_factor,
                            constrain=constrain)


def init_moe_params(key, d_model: int, moe: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    scale = 0.02
    p = {
        "router": jax.random.normal(ks[0], (d_model, moe.num_experts), jnp.float32) * scale,
        "w_gate": jax.random.normal(ks[1], (moe.num_experts, d_model, moe.expert_ff), dtype) * scale,
        "w_up": jax.random.normal(ks[2], (moe.num_experts, d_model, moe.expert_ff), dtype) * scale,
        "w_down": jax.random.normal(ks[3], (moe.num_experts, moe.expert_ff, d_model), dtype) * scale,
    }
    if moe.shared_expert_ff:
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(kk[0], (d_model, moe.shared_expert_ff), dtype) * scale,
            "w_up": jax.random.normal(kk[1], (d_model, moe.shared_expert_ff), dtype) * scale,
            "w_down": jax.random.normal(kk[2], (moe.shared_expert_ff, d_model), dtype) * scale,
        }
    return p
