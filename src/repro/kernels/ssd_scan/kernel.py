"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the GPU SSD implementation leans on warp
shuffles and shared-memory chunk staging; here the chunk loop is the
innermost (sequential) grid dimension, the inter-chunk SSM state [P, N]
lives in VMEM scratch, and the intra-chunk work is expressed as three
MXU matmuls per (batch, head, chunk): CB^T [Q,Q], (CB*L)@dtx [Q,P], and
the state outer product dtx^T@(decay*B) [P,N].

Grid: (B, H, num_chunks), chunk sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_out_ref, state_ref,
            *, num_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)           # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # [Q]
    A = a_ref[0]                                     # scalar (negative)
    Bm = b_ref[0, :, 0].astype(jnp.float32)          # [Q, N]
    Cm = c_ref[0, :, 0].astype(jnp.float32)          # [Q, N]

    a = dt * A                                       # [Q] log-decay
    cum = jnp.cumsum(a)                              # [Q]
    q = x.shape[0]
    seg = cum[:, None] - cum[None, :]                # segsum
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(tri, jnp.exp(seg), 0.0)            # [Q, Q]

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    dtx = x * dt[:, None]                            # [Q, P]
    y = jax.lax.dot_general(cb * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q,P]

    # inter-chunk contribution from the carried state
    state = state_ref[...]                           # [P, N]
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # Cm @ state^T -> [Q,P]

    # state update: decay + chunk contribution
    decay_to_end = jnp.exp(cum[-1] - cum)            # [Q]
    st_new = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        dtx, Bm * decay_to_end[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [P, N]
    state_ref[...] = st_new

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _final():
        st_out_ref[0, 0] = st_new.astype(st_out_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x: [b,s,h,p]; dt: [b,s,h]; A: [h]; B,C: [b,s,g,n] (h % g == 0).

    Returns (y [b,s,h,p], final_state [b,h,p,n]).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    rep = h // g

    kernel = functools.partial(_kernel, num_chunks=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, q, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, q, 1, n), lambda ib, ih, ic, rep=rep:
                         (ib, ic, ih // rep, 0)),
            pl.BlockSpec((1, q, 1, n), lambda ib, ih, ic, rep=rep:
                         (ib, ic, ih // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.astype(jnp.float32), dt.astype(jnp.float32), A.astype(jnp.float32),
      B.astype(jnp.float32), C.astype(jnp.float32))
    return y, st
