"""Flash attention as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the CUDA flash-attention tiling (warps over
128-thread blocks, shared-memory staging) is re-thought for the TPU memory
hierarchy — HBM -> VMEM block staging driven by BlockSpecs, MXU-aligned
(block_q x block_k) score tiles, online-softmax state (m, l, acc) carried in
VMEM scratch across the kv grid dimension, and causal/window block SKIPPING
expressed through the grid index map (fully-masked tiles never leave HBM).

Grid: (batch*heads, num_q_blocks, num_kv_blocks); kv is the innermost
(sequential) dimension so scratch accumulates across it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, logit_cap: float,
            block_q: int, block_k: int, num_kv_blocks: int, sk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # [bq, d]
    k = k_ref[0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = jnp.tanh(s / logit_cap) * logit_cap

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                           # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                   # [bq, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D] -> [B, Hq, Sq, D].

    GQA is handled by the k/v index maps (q head h reads kv head
    h // (Hq//Hkv)) — no materialised repeat.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        return ((bh // hq) * hkv + (bh % hq) // rep, ik, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, block_q=block_q, block_k=block_k,
        num_kv_blocks=nk, sk=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
