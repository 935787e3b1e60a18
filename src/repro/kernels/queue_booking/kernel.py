"""Fused intra-block best-fit booking as a Pallas kernel.

The blocked event-replay substrate (``repro.sim.scan_core``) chunks each
trial's ready-sorted task stream into blocks of B events and carries only
the per-worker free-at vector between blocks.  On accelerators the jnp
form of that loop still round-trips the W-vector and the block's outputs
through HBM once per block; this kernel keeps the whole resolution in
VMEM instead — the free-at vector lives in a VMEM scratch that persists
across the (sequential) block grid dimension, each block's events are
resolved by an in-register ``fori_loop`` over the same fused
best-fit/earliest-free key as ``scan_core.bestfit_book_step``, and one
(1, B) tile per output leaves the core per block.

Grid: (trials, num_blocks), blocks sequential innermost.  One-hot
row/column selects only (no dynamic loads/stores inside the loop) — the
same discipline the jnp engines use, and what the TPU vector unit wants.

Layout: every operand is viewed as (T, 1, ·) with the trial dimension
squeezed out of the block, so each block's last two dimensions are
(1, B) / (1, W) — equal to the array's own (1, ·) row.  Mosaic requires
a block's last two dimensions to be (8, 128)-divisible or whole; a (1, B)
block on a (T, N) array is neither.  B must be a multiple of 128 lanes
(or the whole stream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(wf0_ref, r_ref, s_ref, fin_ref, st_ref, wk_ref, wf_out_ref,
            wf_ref, *, num_blocks: int, block: int, W: int):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        wf_ref[...] = wf0_ref[...]

    r = r_ref[...]                                    # (1, B)
    s = s_ref[...]                                    # (1, B)
    col = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    wcol = lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def body(i, carry):
        wf, fin, st, wk = carry
        sel = col == i
        r_i = jnp.max(jnp.where(sel, r, -jnp.inf))
        s_i = jnp.sum(jnp.where(sel, s, 0.0))
        live = r_i < jnp.inf
        # fused best-fit key: free workers (wf <= r) rank by wf, busy by
        # -wf; -max(key) is the booking-delay floor (scan_core's step)
        key = jnp.where(wf <= r_i, wf, -wf)
        kmax = jnp.max(key)
        # the first worker attaining the max, as XLA's argmax picks it
        w = jnp.min(jnp.where(key == kmax, wcol, W))
        st_i = jnp.maximum(r_i, -kmax)
        f_i = st_i + s_i
        w_hot = wcol == w
        wf2 = jnp.where(w_hot & live, f_i, wf)
        fin2 = jnp.where(sel, jnp.where(live, f_i, jnp.inf), fin)
        st2 = jnp.where(sel, jnp.where(live, st_i, jnp.inf), st)
        wk2 = jnp.where(sel, jnp.where(live, w, jnp.int32(-1)), wk)
        return wf2, fin2, st2, wk2

    wf, fin, st, wk = lax.fori_loop(
        0, block, body,
        (wf_ref[...], jnp.zeros((1, block), jnp.float32),
         jnp.zeros((1, block), jnp.float32),
         jnp.zeros((1, block), jnp.int32)))
    fin_ref[...] = fin
    st_ref[...] = st
    wk_ref[...] = wk
    wf_ref[...] = wf

    @pl.when(ib == num_blocks - 1)
    def _final():
        wf_out_ref[...] = wf


def queue_booking(ready, service, wf0, *, block: int = 128,
                  interpret: bool = False):
    """ready/service: (T, N) ready-sorted event streams (N a multiple of
    ``block``; pad with ready=inf, service=0 — dead events book nothing);
    wf0: (T, W) entry free-at vectors.

    Returns (fin (T, N), start (T, N), worker (T, N) int32, wf (T, W)).
    """
    T, N = ready.shape
    W = wf0.shape[1]
    if N % block or (block % 128 and block != N):
        raise ValueError(f"block {block} must divide the stream length {N} "
                         "and be a multiple of 128 lanes (or all of it)")
    nb = N // block

    row = pl.BlockSpec((pl.Squeezed(), 1, block), lambda it, ib: (it, 0, ib))
    pool = pl.BlockSpec((pl.Squeezed(), 1, W), lambda it, ib: (it, 0, 0))
    kernel = functools.partial(_kernel, num_blocks=nb, block=block, W=W)
    fin, st, wk, wf = pl.pallas_call(
        kernel,
        grid=(T, nb),
        in_specs=[pool, row, row],
        out_specs=[row, row, row, pool],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1, N), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, N), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, N), jnp.int32),
            jax.ShapeDtypeStruct((T, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(wf0.astype(jnp.float32)[:, None], ready.astype(jnp.float32)[:, None],
      service.astype(jnp.float32)[:, None])
    return fin[:, 0], st[:, 0], wk[:, 0], wf[:, 0]
