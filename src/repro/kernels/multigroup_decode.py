"""Pallas TPU kernel: batched multi-group parity decode.

Under load, a batch-atomic completion (threads engine) or a batched DES
finish event makes SEVERAL coding groups decode-ready at the same instant.
Per-group ``decode_one`` calls pay one kernel launch each; this module
decodes ALL recoverable groups in one launch by stacking the per-group
``(parity_out, outputs, coeffs)`` triples:

    recon[g] = ( P[g] - sum_i avail_c[g, i] * F(X_i)[g] ) * inv_c[g]

The per-group coefficient vectors fold the "which member is missing" control
flow into data (0 at the missing index, 1/c_missing appended), so one kernel
serves every per-group missing pattern — the same trick as
``parity_decode``, batched over the leading group axis.  The grid tiles
(G, B, V); feature tiles lane-aligned, batch tiles sublane-aligned.

``multigroup_lstsq`` is the r>1 / multi-missing generalization: the masked
least-squares decode of ALL stacked groups as a single vmapped XLA
computation (one launch).  Per the scheme-layer rule, the tiny [k, k] solve
itself stays in jnp — only its batching moves here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mg_decode_kernel(c_ref, p_ref, outs_ref, o_ref, *, k):
    # c_ref [G, k+1] whole in SMEM (avail coeffs + inv_c per group);
    # p_ref [1, bb, bv]; outs_ref [1, k, bb, bv]; o_ref [1, bb, bv]
    g = pl.program_id(0)
    acc = p_ref[0].astype(jnp.float32)
    for i in range(k):
        acc -= outs_ref[0, i].astype(jnp.float32) * c_ref[g, i]
    o_ref[0] = (acc * c_ref[g, k]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_v",
                                             "interpret"))
def multigroup_decode(parity_outs, outputs, cmat, *, block_b=8, block_v=512,
                      interpret=False):
    """parity_outs [G, B, V]; outputs [G, k, B, V]; cmat [G, k+1] — per-group
    availability-masked coeffs (0 at the missing index) with 1/c_missing
    appended.  Returns reconstructions [G, B, V]."""
    G, k, B, V = outputs.shape
    block_b = min(block_b, B)
    block_v = min(block_v, V)
    grid = (G, pl.cdiv(B, block_b), pl.cdiv(V, block_v))
    return pl.pallas_call(
        functools.partial(_mg_decode_kernel, k=k),
        grid=grid,
        in_specs=[
            # the tiny [G, k+1] table lives whole in SMEM: a (1, k+1) row
            # block is not a legal (8, 128)-aligned VMEM tile
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_b, block_v), lambda g, b, v: (g, b, v)),
            pl.BlockSpec((1, k, block_b, block_v),
                         lambda g, b, v: (g, 0, b, v)),
        ],
        out_specs=pl.BlockSpec((1, block_b, block_v),
                               lambda g, b, v: (g, b, v)),
        out_shape=jax.ShapeDtypeStruct((G, B, V), parity_outs.dtype),
        interpret=interpret,
    )(cmat, parity_outs, outputs)


@jax.jit
def multigroup_lstsq(coeffs, parity_outs, outputs, missing_masks,
                     parity_avail):
    """Batched masked least-squares decode over G stacked groups.

    coeffs [r, k] (shared — one scheme decodes the whole batch);
    parity_outs [G, r, ...]; outputs [G, k, ...]; missing_masks [G, k] bool;
    parity_avail [G, r] bool.  Returns [G, k, ...] with reconstructed rows at
    the missing positions (same normal-equations math as
    ``LinearScheme.decode``, vmapped so every group solves in one launch)."""
    coeffs = coeffs.astype(jnp.float32)
    k = coeffs.shape[1]

    def one(po, outs, mm, pa):
        C = coeffs * pa.astype(jnp.float32)[:, None]
        po = po.astype(jnp.float32) * pa.reshape(
            (-1,) + (1,) * (po.ndim - 1))
        outs = outs.astype(jnp.float32)
        avail = (~mm).astype(jnp.float32)
        rhs = po - jnp.einsum("rk,k...->r...", C * avail[None, :], outs)
        M = C * mm.astype(jnp.float32)[None, :]
        G = M.T @ M + 1e-9 * jnp.eye(k)
        mt_rhs = jnp.einsum("rk,r...->k...", M, rhs)
        sol = jnp.linalg.solve(G, mt_rhs.reshape(k, -1)).reshape(
            mt_rhs.shape)
        mmr = mm.reshape((k,) + (1,) * (outs.ndim - 1))
        return jnp.where(mmr, sol, outs)

    return jax.vmap(one)(jnp.asarray(parity_outs), jnp.asarray(outputs),
                         jnp.asarray(missing_masks, bool),
                         jnp.asarray(parity_avail, bool))
