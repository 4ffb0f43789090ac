"""Pure-jnp oracles for every Pallas kernel. The kernel tests sweep shapes
and dtypes and assert allclose against these."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def parity_encode_ref(queries, coeffs):
    """queries [k, B, F]; coeffs [k] -> parity [B, F] (fp32 accumulate)."""
    acc = jnp.einsum("k,kbf->bf", coeffs.astype(jnp.float32),
                     queries.astype(jnp.float32))
    return acc.astype(queries.dtype)


def parity_decode_ref(parity_out, outputs, avail_coeffs, inv_c):
    """parity_out [B, V]; outputs [k, B, V]; avail_coeffs [k] (0 at the
    missing index, code coefficient elsewhere); inv_c scalar = 1/c_missing.
    Returns reconstruction [B, V]."""
    s = jnp.einsum("k,kbv->bv", avail_coeffs.astype(jnp.float32),
                   outputs.astype(jnp.float32))
    return ((parity_out.astype(jnp.float32) - s) * inv_c).astype(
        parity_out.dtype)


def fused_encode_forward_ref(queries, coeffs, weights):
    """queries [k, B, F]; coeffs [r, k]; weights [r, F, V] (one first-layer
    matrix per parity row) -> [r, B, V]: encode over the coding dim, then
    each row's first forward matmul (fp32 accumulate throughout)."""
    enc = jnp.einsum("rk,kbf->rbf", coeffs.astype(jnp.float32),
                     queries.astype(jnp.float32))
    out = jnp.einsum("rbf,rfv->rbv", enc, weights.astype(jnp.float32))
    return out.astype(queries.dtype)


def learned_project_ref(h, w):
    """h [H, B, F]; w [H, r] -> [r, B, F]: out[j] = sum_h W[h, j] * H[h]
    (fp32 accumulate)."""
    out = jnp.einsum("hr,hbf->rbf", w.astype(jnp.float32),
                     h.astype(jnp.float32))
    return out.astype(h.dtype)


def berrut_encode_ref(q, c):
    """q [k, B, F]; c [r, k] -> [r, B, F]: out[j] = sum_i C[j, i] * Q[i]."""
    return learned_project_ref(q, c.T)


def multigroup_decode_ref(parity_outs, outputs, cmat):
    """parity_outs [G, B, V]; outputs [G, k, B, V]; cmat [G, k+1] (per-group
    availability-masked coeffs, 0 at the missing index, with 1/c_missing
    appended).  Returns [G, B, V] — the batched subtraction decode."""
    k = outputs.shape[1]
    s = jnp.einsum("gk,gkbv->gbv", cmat[:, :k].astype(jnp.float32),
                   outputs.astype(jnp.float32))
    inv = cmat[:, k].astype(jnp.float32)[:, None, None]
    return ((parity_outs.astype(jnp.float32) - s) * inv).astype(
        parity_outs.dtype)


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q [B,Sq,H,hd]; k,v [B,Sk,KV,hd] -> [B,Sq,H,hd] (naive softmax)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    valid = jnp.ones((Sq, Sk), bool)
    if causal:
        valid &= kpos <= qpos
    if window:
        valid &= kpos > qpos - window
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, pos):
    """q [B,H,hd]; caches [B,S,KV,hd]; pos scalar (valid slots: <= pos).
    Returns [B,H,hd]."""
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    if KV != H:
        k_cache = jnp.repeat(k_cache, H // KV, axis=2)
        v_cache = jnp.repeat(v_cache, H // KV, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * hd ** -0.5
    valid = jnp.arange(S)[None, None, :] <= pos
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)
