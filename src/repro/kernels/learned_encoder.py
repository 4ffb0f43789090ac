"""Pallas TPU kernel: learned-encoder final projection — the linear map
from the encoder MLP's hidden activations to the r parity rows,

    out[j] = sum_h W[h, j] * H[h]          (H [H, B, F], W [H, r])

Structurally the same memory-bound reduction as parity encoding, but over
the hidden dimension H instead of the coding dimension k, with all r output
rows produced by one launch.  The grid tiles (r, B, F); each program
instance streams its H input tiles HBM->VMEM and accumulates one output row
tile in fp32 VREGs.  Feature tiles are lane-aligned (multiples of 128),
batch tiles sublane-aligned (multiples of 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _project_kernel(w_ref, h_ref, o_ref, *, hidden):
    # w_ref: [H, r] whole in SMEM; h_ref: [H, bb, bf]; o_ref: [1, bb, bf]
    j = pl.program_id(0)
    acc = h_ref[0].astype(jnp.float32) * w_ref[0, j]
    for i in range(1, hidden):
        acc += h_ref[i].astype(jnp.float32) * w_ref[i, j]
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_f",
                                             "interpret"))
def learned_project(h, w, *, block_b=8, block_f=512, interpret=False):
    """h [H, B, F]; w [H, r] -> [r, B, F]."""
    H, B, F = h.shape
    r = w.shape[1]
    block_b = min(block_b, B)
    block_f = min(block_f, F)
    grid = (r, pl.cdiv(B, block_b), pl.cdiv(F, block_f))
    return pl.pallas_call(
        functools.partial(_project_kernel, hidden=H),
        grid=grid,
        in_specs=[
            # the tiny [H, r] weight lives whole in SMEM: an (H, 1) column
            # block is not a legal (8, 128)-aligned VMEM tile once r > 1
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((H, block_b, block_f), lambda j, i, b: (0, i, b)),
        ],
        out_specs=pl.BlockSpec((1, block_b, block_f),
                               lambda j, i, b: (j, i, b)),
        out_shape=jax.ShapeDtypeStruct((r, B, F), h.dtype),
        interpret=interpret,
    )(w.astype(jnp.float32), h)
