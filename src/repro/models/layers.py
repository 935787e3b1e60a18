"""Core layers: norms, rotary embeddings, MLP variants, attention.

Three attention execution strategies, chosen by the caller per shape so that
every (arch x shape) cell lowers with a sane memory footprint AND with FLOPs
that are visible to ``compiled.cost_analysis()`` wherever possible:

- ``attention_full``      : materialised scores, causal/window mask.  Used for
                            train_4k (S<=4k) and for single-token decode.
- ``attention_blockwise`` : flash-style running-softmax scan over KV chunks.
                            Used for 32k global-attention prefill.  The scan
                            body is counted ONCE by cost_analysis; the known
                            trip count must be corrected for analytically.
- ``attention_sliding_blocked`` : sliding-window attention computed on
                            (block, 2*window) tiles with no scan — exact for
                            local layers and fully FLOP-visible.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38  # large negative for masking (bf16-safe)


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


def mlp_block(x, p, variant: str):
    """SwiGLU / GeGLU gated MLP."""
    gate = x @ p["w_gate"]
    up = x @ p["w_up"]
    act = jax.nn.silu(gate) if variant == "swiglu" else jax.nn.gelu(gate, approximate=True)
    return (act * up) @ p["w_down"]


# --------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [B, S, H, hd]; positions: [B, S] int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # [hd/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [B, S, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, theta: float, sections):
    """qwen2-vl multimodal RoPE.  positions_thw: [3, B, S] (t, h, w ids).

    The rotary spectrum is partitioned into ``sections`` (halved-dim units);
    each section takes its angle from the matching positional stream.
    """
    import numpy as np
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                              # [hd/2]
    ang_each = positions_thw.astype(jnp.float32)[..., None] * freqs  # [3, B, S, hd/2]
    idx = jnp.asarray(np.repeat(np.arange(3), np.asarray(sections)))  # [hd/2] static
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang_each, 0, -1), idx[None, None, :, None], axis=-1
    )[..., 0]                                                  # [B, S, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention cores
# --------------------------------------------------------------------------

def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def attention_full(q, k, v, *, causal: bool, window: int = 0,
                   logit_cap: float = 0.0, scale: float, q_offset=0,
                   kv_len: Optional[jnp.ndarray] = None):
    """Materialised-scores attention.

    q: [B, Sq, Hq, hd]; k, v: [B, Sk, Hkv, hd].
    ``q_offset``: absolute position of q[0] (decode: cache index).
    ``kv_len``: optional valid KV length (decode with preallocated cache).
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = softcap(scores, logit_cap)
    qpos = jnp.arange(sq)[:, None] + q_offset                  # [Sq,1]
    kpos = jnp.arange(sk)[None, :]                             # [1,Sk]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask = mask & (kpos < kv_len)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_blockwise(q, k, v, *, causal: bool, logit_cap: float = 0.0,
                        scale: float, chunk: int = 1024):
    """Flash-style attention: scan over KV chunks with running max/denom.

    Exact (same math as flash attention); memory O(Sq * chunk).  Trip count
    = Sk // chunk (corrected for in the roofline FLOP accounting).
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    assert sk % chunk == 0, (sk, chunk)
    n_chunks = sk // chunk
    k = k.reshape(b, n_chunks, chunk, hkv, hd)
    v = v.reshape(b, n_chunks, chunk, hkv, hd)
    n_rep = hq // hkv

    qpos = jnp.arange(sq)[:, None]

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc, ci = inputs                                    # [b,chunk,hkv,hd], idx
        kc = _repeat_kv(kc, n_rep)
        vc = _repeat_kv(vc, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32) * scale
        s = softcap(s, logit_cap)
        if causal:
            kpos = ci * chunk + jnp.arange(chunk)[None, :]
            s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, hq, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (k.swapaxes(0, 1), v.swapaxes(0, 1), jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)                  # [B,Sq,H,hd]


def attention_sliding_blocked(q, k, v, *, window: int, logit_cap: float = 0.0,
                              scale: float):
    """Causal sliding-window attention on (block, 2*window) tiles, no scan.

    Each block of ``window`` queries attends to [its block, previous block];
    with causal+window masking inside the tile this is exact sliding-window
    attention.  FLOPs ~ 2 * S * window per head-dim unit, all visible to
    cost_analysis.
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    w = window
    assert s % w == 0, (s, w)
    nb = s // w
    qb = q.reshape(b, nb, w, hq, hd)
    kb = k.reshape(b, nb, w, hq, hd)
    vb = v.reshape(b, nb, w, hq, hd)
    # previous block (zeros before block 0)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)                  # [b,nb,2w,h,d]
    v2 = jnp.concatenate([vprev, vb], axis=2)
    scores = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, k2).astype(jnp.float32) * scale
    scores = softcap(scores, logit_cap)
    qpos = jnp.arange(w)[:, None] + w                          # within 2w frame
    kpos = jnp.arange(2 * w)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - w)
    first = (jnp.arange(nb) == 0)[None, :, None, None, None]
    valid = jnp.where(first & (kpos < w)[None, None, None], False, mask[None, None, None])
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, v2)
    return out.reshape(b, s, hq, hd)
