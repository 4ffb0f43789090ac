"""Pallas TPU kernel: fused parity encode -> first forward matmul.

The coded hot path for linear/MLP parity substrates runs encode (the [r, k]
projection over the coding dimension) and the parity model's first matmul as
SEPARATE launches today, materialising the [r, B, F] encoded queries in HBM
between them.  This kernel fuses the two:

    out[j, b, v] = sum_f ( sum_i C[j, i] * X[i, b, f] ) * W[j, f, v]

Queries are flattened to [k, B, F]; each parity row j carries its OWN
first-layer weight matrix W[j] (parity models are trained independently per
row).  The grid tiles (r, B, V, F): a program instance streams its k query
tiles HBM->VMEM, accumulates the encoded tile in fp32 VREGs, multiplies it
into W[j]'s tile on the MXU and accumulates the product into an fp32 VMEM
scratch over the F (contraction) grid axis — the innermost axis, so the
output block is revisited and flushed once on the last F step.  Feature and
value tiles are lane-aligned (multiples of 128), batch tiles sublane-aligned
(multiples of 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(c_ref, q_ref, w_ref, o_ref, acc_ref, *, k, nf, f_total,
                  block_f):
    # c_ref [r, k] whole in SMEM; q_ref [k, bb, bf]; w_ref [1, bf, bv];
    # o_ref [1, bb, bv]; acc_ref [bb, bv] fp32 scratch, live across the F
    # grid axis
    j = pl.program_id(0)
    f = pl.program_id(3)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    enc = q_ref[0].astype(jnp.float32) * c_ref[j, 0]
    for i in range(1, k):
        enc += q_ref[i].astype(jnp.float32) * c_ref[j, i]
    w = w_ref[0].astype(jnp.float32)
    if f_total % block_f:
        # a trailing partial F block is padded with UNDEFINED values — zero
        # the invalid tail of BOTH operands (0 * garbage/NaN != 0)
        valid = (f * block_f +
                 jax.lax.broadcasted_iota(jnp.int32, (1, block_f), 1)
                 ) < f_total
        enc = jnp.where(valid, enc, 0.0)
        w = jnp.where(valid.reshape(block_f, 1), w, 0.0)
    acc_ref[...] += jnp.dot(enc, w)

    @pl.when(f == nf - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_f", "block_v",
                                             "interpret"))
def fused_encode_forward(queries, coeffs, weights, *, block_b=8, block_f=512,
                         block_v=128, interpret=False):
    """queries [k, B, F]; coeffs [r, k]; weights [r, F, V] -> [r, B, V]."""
    k, B, F = queries.shape
    r, _, V = weights.shape
    block_b = min(block_b, B)
    block_f = min(block_f, F)
    block_v = min(block_v, V)
    nf = pl.cdiv(F, block_f)
    grid = (r, pl.cdiv(B, block_b), pl.cdiv(V, block_v), nf)
    return pl.pallas_call(
        functools.partial(_fused_kernel, k=k, nf=nf, f_total=F,
                          block_f=block_f),
        grid=grid,
        in_specs=[
            # coeffs [r, k] whole in SMEM: a (1, k) row block is not a
            # legal (8, 128)-aligned VMEM tile once r > 1
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k, block_b, block_f),
                         lambda j, b, v, f: (0, b, f)),
            pl.BlockSpec((1, block_f, block_v),
                         lambda j, b, v, f: (j, f, v)),         # W[j] tile
        ],
        out_specs=pl.BlockSpec((1, block_b, block_v),
                               lambda j, b, v, f: (j, b, v)),
        out_shape=jax.ShapeDtypeStruct((r, B, V), queries.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_v), jnp.float32)],
        interpret=interpret,
    )(coeffs.astype(jnp.float32), queries, weights)
