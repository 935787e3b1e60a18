"""Sharding plan: logical roles -> PartitionSpecs, with divisibility fallbacks.

Baseline parallelism (DESIGN.md §5):
- batch           -> ("pod","data")          data parallelism (+ flight axis)
- weight dim0/in  -> "data"                  ZeRO-3/FSDP parameter sharding
- weight out/TP   -> "model"                 tensor parallelism (heads/ff/vocab)
- experts         -> "model"                 expert parallelism
- activations     -> constrained at key points via ``plan.constrain``

Every rule checks divisibility and degrades to replication rather than
erroring, so all ten architectures (incl. 40-expert / 12-head / odd-vocab
configs) lower on the fixed 16x16 and 2x16x16 meshes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import batch_axes


def _axes_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@dataclasses.dataclass
class Plan:
    mesh: Any
    cfg: ModelConfig
    # axis-name knobs (overridable for perf experiments)
    data: Any = None          # filled in __post_init__
    model: str = "model"
    zero3: bool = True        # shard params+opt state over data axis
    # §Perf variants:
    seq_parallel: Optional[bool] = None  # residual sharded over model on seq;
    # None = auto: ON for archs whose head count doesn't divide the model
    # axis (measured 2-2.5x on the collective term, EXPERIMENTS.md §Perf)
    moe_token_align: bool = False  # pre-shard tokens to the EP layout

    def __post_init__(self):
        self.data = batch_axes(self.mesh)
        if self.seq_parallel is None:
            tp = _axes_size(self.mesh, self.model)
            self.seq_parallel = bool(self.cfg.num_heads
                                     and self.cfg.num_heads % tp != 0)

    # -- helpers ------------------------------------------------------------
    def _ok(self, dim: int, axes) -> bool:
        n = _axes_size(self.mesh, axes)
        return n > 1 and dim % n == 0

    def _pick(self, shape, rules):
        """rules: list of (dim_index, axes) applied if divisible & unused."""
        spec = [None] * len(shape)
        used = set()
        for d, axes in rules:
            if axes is None:
                continue
            key = tuple(axes) if not isinstance(axes, str) else (axes,)
            if any(a in used for a in key):
                continue
            if self._ok(shape[d], axes) and spec[d] is None:
                spec[d] = axes
                used.update(key)
        return P(*spec)

    # -- parameters ---------------------------------------------------------
    def param_spec(self, path: str, shape) -> P:
        """PartitionSpec for a parameter, keyed by its pytree path string."""
        name = path.split("/")[-1]
        fsdp = self.data if self.zero3 else None
        if name == "embed":
            return self._pick(shape, [(0, self.model), (1, fsdp)])
        if name == "lm_head":
            return self._pick(shape, [(1, self.model), (0, fsdp)])
        if name == "router":
            return self._pick(shape, [(0, fsdp)])
        if name in ("w_gate", "w_up") and len(shape) == 3:   # MoE experts [E,D,F]
            return self._pick(shape, [(0, self.model), (1, fsdp), (2, self.model)])
        if name == "w_down" and len(shape) == 3:             # [E,F,D]
            return self._pick(shape, [(0, self.model), (1, self.model), (2, fsdp)])
        if name in ("wq", "wk", "wv", "w_gate", "w_up",
                    "in_z", "in_x", "in_B", "in_C", "in_dt"):
            return self._pick(shape, [(0, fsdp), (1, self.model)])
        if name in ("wo", "w_down", "out_proj"):
            return self._pick(shape, [(0, self.model), (1, fsdp)])
        if name in ("conv_x_w", "conv_B_w", "conv_C_w"):
            return self._pick(shape, [(1, self.model)])
        return P()  # norms, biases, A_log, dt_bias, D: replicated

    def param_shardings(self, params_shape):
        """Map a params pytree (of ShapeDtypeStruct or arrays) to shardings."""
        def one(path, leaf):
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            return NamedSharding(self.mesh, self.param_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(one, params_shape)

    # -- activations --------------------------------------------------------
    def act_spec(self, role: str, shape) -> Optional[P]:
        cfg = self.cfg
        b = self.data
        if role == "act_resid":                              # [B,S,D]
            if self.seq_parallel and self._ok(shape[1], self.model):
                return P(b, self.model, None)
            return P(b, None, None)
        if role == "moe_tokens":                             # [T,D] pre-EP
            if not self.moe_token_align:
                return None                                  # baseline
            axes = (*b, self.model)
            if self._ok(shape[0], axes):
                return P(axes, None)
            return P(b, None)
        if role == "act_heads":                              # [B,S,H,hd]
            rules = [(0, b)]
            rules.append((2, self.model) if self._ok(shape[2], self.model)
                         else (1, self.model))
            return self._pick(shape, rules)
        if role == "act_kv_heads":
            rules = [(0, b)]
            if self._ok(shape[2], self.model):
                rules.append((2, self.model))
            return self._pick(shape, rules)
        if role == "act_ff_out":
            return P(b, None, None)
        if role == "logits":                                 # [B,S,V]
            if self._ok(shape[-1], self.model):
                return P(b, None, self.model)
            return self._pick(shape, [(0, b), (1, self.model)])
        if role == "moe_logits":                             # [T,E]
            return P(b, None)
        if role == "moe_buffer":                             # [E,C,D]
            rules = []
            if self._ok(shape[0], self.model):
                rules.append((0, self.model))
            rules.append((1, b))
            return self._pick(shape, rules)
        if role == "moe_w_in":                               # [E,D,F] compute
            if self._ok(shape[0], self.model):
                return P(self.model, None, None)
            return self._pick(shape, [(2, self.model)])
        if role == "moe_w_out":                              # [E,F,D] compute
            if self._ok(shape[0], self.model):
                return P(self.model, None, None)
            return self._pick(shape, [(1, self.model)])
        if role == "ssm_inner":                              # [B,S,din]
            return self._pick(shape, [(0, b), (2, self.model)])
        if role == "kv_cache":                               # [B,C,hkv,hd]
            rules = [(0, b)] if shape[0] > 1 else [(1, b)]   # seq-shard for B=1
            if self._ok(shape[2], self.model):
                rules.append((2, self.model))
            else:
                # kv heads don't divide the model axis: shard the SEQ dim.
                # (head_dim sharding makes GSPMD all-gather the whole cache
                # — measured 43 GB/step on gemma2 decode_32k; seq sharding
                # keeps the contraction local and the softmax reduction is
                # scalar-sized.)
                rules.append((1, self.model))
            return self._pick(shape, rules)
        if role == "ssm_state":                              # [B,H,P,N]
            rules = [(0, b)] if shape[0] > 1 else []
            if self._ok(shape[1], self.model):
                rules.append((1, self.model))
            return self._pick(shape, rules)
        if role == "conv_cache":                             # [B,K-1,C]
            rules = [(0, b)] if shape[0] > 1 else []
            if self._ok(shape[2], self.model):
                rules.append((2, self.model))
            return self._pick(shape, rules)
        return None

    def constrain(self, t, role: str):
        spec = self.act_spec(role, t.shape)
        if spec is None:
            return t
        try:
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, spec))
        except ValueError:
            return t

    # -- batches / caches ---------------------------------------------------
    def batch_shardings(self, batch_shape):
        b = self.data

        def one(path, leaf):
            name = str(getattr(path[-1], "key", "")) if path else ""
            if name == "positions" and len(leaf.shape) == 3:  # mrope [3,B,S]
                return NamedSharding(self.mesh, P(None, b, None))
            spec = [None] * len(leaf.shape)
            if leaf.shape and leaf.shape[0] > 1 and self._ok(leaf.shape[0], b):
                spec[0] = b
            return NamedSharding(self.mesh, P(*spec))
        return jax.tree_util.tree_map_with_path(one, batch_shape)

    def cache_shardings(self, cache_shape):
        def one(path, leaf):
            names = [str(getattr(k, "key", "")) for k in path]
            nm = names[-1]
            if nm in ("k", "v", "cross_k", "cross_v"):
                role = "kv_cache"
            elif nm == "state":
                role = "ssm_state"
            elif nm.startswith("conv"):
                role = "conv_cache"
            else:
                return NamedSharding(self.mesh, P())
            spec = self.act_spec(role, leaf.shape)
            return NamedSharding(self.mesh, spec if spec else P())
        return jax.tree_util.tree_map_with_path(one, cache_shape)
