"""GQA decode attention (flash-decoding style) as a Pallas TPU kernel.

One new token attends over a long KV cache: the cache is streamed through
VMEM in blocks along the sequence (grid dim 1, sequential), with the online
softmax state for all query heads held in VMEM scratch.  This is the
memory-bound serving hot loop — arithmetic intensity ~ O(Hq/Hkv) — so the
kernel's job is purely to keep the HBM stream dense and skip invalid ring
slots via the position mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, logit_cap: float, rep: int, num_blocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hq, d = q_ref.shape[1], q_ref.shape[2]
    hkv = hq // rep
    q = q_ref[0].astype(jnp.float32).reshape(hkv, rep, d)
    k = k_ref[0].astype(jnp.float32)                # [bk, hkv, d]
    v = v_ref[0].astype(jnp.float32)
    # s[g, r, bk] = sum_d q[g,r,d] * k[bk,g,d]
    s = jax.lax.dot_general(
        q, k.transpose(1, 2, 0), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = jnp.tanh(s / logit_cap) * logit_cap
    valid = (pos_ref[...] >= 0)[None, None, :]      # [1,1,bk]
    s = jnp.where(valid, s, NEG_INF)

    s2 = s.reshape(hq, -1)                          # [hq, bk]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
    p = jnp.exp(s2 - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    # acc[g, r, d] += p[g, r, bk] @ v[bk, g, d]
    pv = jax.lax.dot_general(
        p.reshape(hkv, rep, -1), v.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv.reshape(hq, d)
    m_ref[...] = m_new

    @pl.when(ik == num_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, kv_pos, *, scale: float | None = None,
                     logit_cap: float = 0.0, block_k: int = 512,
                     interpret: bool = False):
    """q: [B, Hq, D]; k, v: [B, Sk, Hkv, D]; kv_pos: [Sk] -> [B, Hq, D]."""
    b, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    block_k = min(block_k, sk)
    nk = pl.cdiv(sk, block_k)

    kernel = functools.partial(_kernel, scale=scale, logit_cap=logit_cap,
                               rep=rep, num_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda ib, ik: (ib, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, d), lambda ib, ik: (ib, ik, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, d), lambda ib, ik: (ib, ik, 0, 0)),
            pl.BlockSpec((block_k,), lambda ib, ik: (ik,)),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda ib, ik: (ib, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, kv_pos)
    return out
