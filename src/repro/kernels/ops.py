"""Jit'd public wrappers around the Pallas kernels.

On a TPU these call the Mosaic-compiled kernels; on the CPU backend they
run in ``interpret=True`` mode (the kernel body evaluated as plain JAX ops),
so the same call sites work in tests, benches and the serving runtime.  Any
other backend raises: a kernel never silently falls back to the
interpreter where the device was meant to run it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.parity_encode import parity_encode as _encode
from repro.kernels.parity_decode import parity_decode as _decode
from repro.kernels.fused_encode_forward import (
    fused_encode_forward as _fused_ef)
from repro.kernels.multigroup_decode import multigroup_decode as _mg_decode
from repro.kernels.learned_encoder import learned_project as _project
from repro.kernels.berrut_encoder import berrut_encode as _berrut
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode_attn


def _interpret():
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


def parity_encode_op(queries, coeffs, **kw):
    """queries [k, B, ...] (any trailing feature shape); coeffs [k]."""
    k, B = queries.shape[:2]
    flat = queries.reshape(k, B, -1)
    out = _encode(flat, coeffs, interpret=_interpret(), **kw)
    return out.reshape((B,) + queries.shape[2:])


def parity_decode_op(parity_out, outputs, missing_idx, coeffs=None, **kw):
    """parity_out [B, V]; outputs [k, B, V]; missing_idx python int."""
    k = outputs.shape[0]
    c = jnp.ones((k,), jnp.float32) if coeffs is None else \
        jnp.asarray(coeffs, jnp.float32)
    avail = c * (jnp.arange(k) != missing_idx)
    inv_c = 1.0 / c[missing_idx]
    return _decode(parity_out, outputs, avail, inv_c,
                   interpret=_interpret(), **kw)


def fused_encode_forward_op(queries, coeffs, weights, **kw):
    """Fused coded hot path: encode + the first parity-forward matmul in one
    launch.  queries [k, B, ...] (any trailing feature shape, flattened to
    F); coeffs [r, k]; weights [r, F, V] — one first-layer matrix per parity
    row — returns [r, B, V]."""
    k, B = queries.shape[:2]
    flat = queries.reshape(k, B, -1)
    return _fused_ef(flat, jnp.asarray(coeffs, jnp.float32),
                     jnp.asarray(weights), interpret=_interpret(), **kw)


def multigroup_decode_op(parity_outs, outputs, missing_idxs, coeffs, **kw):
    """Batched r=1 subtraction decode over G stacked groups in one launch.

    parity_outs [G, B, V...] (axis 1 is batch when present: [G, V...] inputs
    are treated as batch 1); outputs [G, k, B, V...]; missing_idxs [G] ints;
    coeffs [k] (shared) or [G, k] (per-group).  Returns reconstructions
    shaped like ``parity_outs``."""
    parity_outs = jnp.asarray(parity_outs)
    outputs = jnp.asarray(outputs)
    G, k = outputs.shape[:2]
    if parity_outs.ndim >= 3:
        B = parity_outs.shape[1]
        po = parity_outs.reshape(G, B, -1)
        outs = outputs.reshape(G, k, B, -1)
    else:
        po = parity_outs.reshape(G, 1, -1)
        outs = outputs.reshape(G, k, 1, -1)
    idx = jnp.asarray(missing_idxs)
    c = jnp.asarray(coeffs, jnp.float32)
    if c.ndim == 1:
        c = jnp.broadcast_to(c[None], (G, k))
    avail = c * (jnp.arange(k)[None, :] != idx[:, None])
    inv = 1.0 / jnp.take_along_axis(c, idx[:, None], axis=1)     # [G, 1]
    cmat = jnp.concatenate([avail, inv], axis=1)                 # [G, k+1]
    out = _mg_decode(po, outs, cmat, interpret=_interpret(), **kw)
    return out.reshape(parity_outs.shape)


def berrut_encode_op(queries, coeffs, **kw):
    """Approxifer encode projection: queries [k, B, ...] (any trailing
    feature shape); coeffs [r, k] -> [r, B, ...], one launch for all r."""
    k, B = queries.shape[:2]
    flat = queries.reshape(k, B, -1)
    out = _berrut(flat, coeffs, interpret=_interpret(), **kw)
    return out.reshape((coeffs.shape[0], B) + queries.shape[2:])


def learned_project_op(h, w, **kw):
    """Learned-encoder final projection: h [H, B, ...] (any trailing feature
    shape); w [H, r] -> [r, B, ...]."""
    hd, B = h.shape[:2]
    flat = h.reshape(hd, B, -1)
    out = _project(flat, w, interpret=_interpret(), **kw)
    return out.reshape((w.shape[1], B) + h.shape[2:])


def flash_attention_op(q, k, v, *, causal=True, window=0, **kw):
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=_interpret(), **kw)


def decode_attention_op(q, k_cache, v_cache, pos, **kw):
    return _decode_attn(q, k_cache, v_cache, pos, interpret=_interpret(),
                        **kw)
