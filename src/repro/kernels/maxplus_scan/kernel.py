"""Factored max-plus block-summary prefix scan as a Pallas kernel.

The log-depth event replay (``repro.sim.scan_core``, ``scan="logdepth"``)
summarizes each resolved block of events as a factored W x W max-plus
operator ``(diag, offset)`` over the per-worker free-at vector —
``apply((d, b), wf) = max(wf + d, b)`` — and needs every block's entry
vector, i.e. the exclusive prefix composition of the whole operator tape
applied to the stream's entry vector.  One trial's entire (nb, W) tape
fits in VMEM; this kernel resolves it in-core with a Hillis-Steele
doubling scan — log2(nb) fused compose sweeps over the resident tape, one
(nb, W) entry tile leaving the core per trial — instead of round-tripping
HBM per compose the way a lowered ``associative_scan`` tree does.

Grid: (trials,), trials parallel.  The compose is the closed form

    compose((d1, b1), (d2, b2)) = (d1 + d2, max(b1 + d2, b2))

("do op1, then op2"); out-of-range shift positions compose with the
identity operator (d = 0, b = -inf).  Static-shape concatenate/slice
shifts only — no dynamic indexing inside the sweep.

Layout: the trial dimension is squeezed out of every block, and the
(T, W) entry/exit vectors are viewed as (T, 1, W), so each block's last
two dimensions equal the array's own — the form Mosaic accepts for
blocks that are not (8, 128)-divisible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(d_ref, b_ref, wf0_ref, ent_ref, wf_ref, *, nb: int, W: int):
    d = d_ref[...]                                    # (nb, W)
    b = b_ref[...]                                    # (nb, W)
    # inclusive Hillis-Steele doubling over the block axis: after the
    # sweep row k holds op_0 ∘ ... ∘ op_k
    s = 1
    while s < nb:
        d_sh = jnp.concatenate(
            [jnp.zeros((s, W), d.dtype), d[:nb - s]], axis=0)
        b_sh = jnp.concatenate(
            [jnp.full((s, W), -jnp.inf, b.dtype), b[:nb - s]], axis=0)
        d, b = d_sh + d, jnp.maximum(b_sh + d, b)
        s *= 2
    # entries: row k applies the EXCLUSIVE prefix (rows < k) to wf0;
    # row 0 composes with the identity, i.e. is wf0 itself
    w0 = wf0_ref[...]                                 # (1, W)
    pd = jnp.concatenate([jnp.zeros((1, W), d.dtype), d[:nb - 1]], axis=0)
    pb = jnp.concatenate(
        [jnp.full((1, W), -jnp.inf, b.dtype), b[:nb - 1]], axis=0)
    ent_ref[...] = jnp.maximum(w0 + pd, pb)
    wf_ref[...] = jnp.maximum(w0 + d[nb - 1:nb], b[nb - 1:nb])


def maxplus_scan(diag, off, wf0, *, interpret: bool = False):
    """diag/off: (T, nb, W) factored per-block operators; wf0: (T, W)
    entry vectors.  Returns ``(entries (T, nb, W), wf_out (T, W))`` —
    every block's entry vector plus the whole tape applied to ``wf0``.
    """
    T, nb, W = diag.shape
    tape = pl.BlockSpec((pl.Squeezed(), nb, W), lambda t: (t, 0, 0))
    vec = pl.BlockSpec((pl.Squeezed(), 1, W), lambda t: (t, 0, 0))
    kernel = functools.partial(_kernel, nb=nb, W=W)
    ent, wf = pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[tape, tape, vec],
        out_specs=[tape, vec],
        out_shape=[
            jax.ShapeDtypeStruct((T, nb, W), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, W), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(diag.astype(jnp.float32), off.astype(jnp.float32),
      wf0.astype(jnp.float32)[:, None])
    return ent, wf[:, 0]
