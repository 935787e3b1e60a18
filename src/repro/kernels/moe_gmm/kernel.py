"""Capacity-batched expert matmul (MoE grouped GEMM) as a Pallas kernel.

The EP dispatch (repro.models.moe) produces dense [E, C, D] capacity
buffers; expert compute is then an expert-batched GEMM.  Blocks are MXU
aligned, the contraction dim is the innermost (sequential) grid dim with a
f32 VMEM accumulator, and each (expert, row-block, col-block) tile streams
A and W blocks from HBM exactly once.

Grid: (E, C/bc, F/bf, D/bd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, w_ref, o_ref, acc_ref, *, num_k: int):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0].astype(jnp.float32)        # [bc, bd]
    w = w_ref[0].astype(jnp.float32)        # [bd, bf]
    acc_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == num_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def expert_matmul(buf, w, *, block_c: int = 128, block_f: int = 128,
                  block_d: int = 256, interpret: bool = False):
    """buf: [E, C, D]; w: [E, D, F] -> [E, C, F]."""
    e, c, d = buf.shape
    f = w.shape[2]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    nc, nf, nd = pl.cdiv(c, block_c), pl.cdiv(f, block_f), pl.cdiv(d, block_d)

    kernel = functools.partial(_kernel, num_k=nd)
    return pl.pallas_call(
        kernel,
        grid=(e, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda ie, ic, jf, kd: (ie, ic, kd)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda ie, ic, jf, kd: (ie, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda ie, ic, jf, kd: (ie, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), buf.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(buf, w)
