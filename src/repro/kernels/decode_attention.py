"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

The decode hot path is bandwidth-bound: one query vector against S cached
keys/values. Grid (batch, kv_block) streams the cache HBM->VMEM once; all H
query heads ride along in a single [H, hd] VMEM tile, and GQA grouping is a
reshape of the head dim (no repeated KV reads — the XLA fallback's
``jnp.repeat`` re-reads the cache rep times, which this kernel removes; see
EXPERIMENTS.md §Perf). Online softmax scratch persists across the KV sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, block_k, n_kv_blocks, kv_heads, rep):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    H = kv_heads * rep
    q = q_ref[0].astype(jnp.float32) * scale          # [H, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, KV, hd]
    # scores per head: head h uses kv-head h // rep
    qg = q.reshape(kv_heads, rep, -1)                 # [KV, rep, hd]
    s = jnp.einsum("grd,kgd->grk", qg, k)             # [KV, rep, bk]
    s = s.reshape(H, block_k)

    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = kpos <= pos_ref[pl.program_id(0)]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])                   # [H, bk]
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    v = v_ref[0].astype(jnp.float32)                  # [bk, KV, hd]
    pg = p.reshape(kv_heads, rep, block_k)
    o = jnp.einsum("grk,kgd->grd", pg, v).reshape(H, -1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + o
    m_ref[...] = m_new

    @pl.when(kb == n_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
                        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, block_k=128,
                     interpret=False):
    """q [B,H,hd]; caches [B,S,KV,hd]; pos scalar int32 or [B] per-row
    positions (slot-batched decode: each batch row is an independent stream
    at its own position). Returns [B,H,hd].

    ``block_k`` bounds the VMEM working set: the fp32 copies of one K and V
    block and their head-major relayouts cost ~40 bytes per cached element,
    so at KV*hd = 2048 (OLMo-1B) a 256-row block already overflows Mosaic's
    16 MiB scoped-VMEM default on a v5e."""
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    block_k = min(block_k, S)
    nk = pl.cdiv(S, block_k)

    kernel = functools.partial(
        _decode_kernel, scale=hd ** -0.5, block_k=block_k, n_kv_blocks=nk,
        kv_heads=KV, rep=rep)

    # A scalar pos broadcasts to [B]; the whole [B] vector sits in SMEM
    # (a (1,)-block of it is not a legal Mosaic tile) and grid row b reads
    # its own pos_ref[b], so per-row positions reuse the same kernel body.
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    return pl.pallas_call(
        kernel,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # pos [B]
            pl.BlockSpec((1, H, hd), lambda b, j: (b, 0, 0)),      # q
            pl.BlockSpec((1, block_k, KV, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, KV, hd), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
        interpret=interpret,
    )(pos, q, k_cache, v_cache)
