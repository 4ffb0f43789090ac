"""Core transformer layers: norms, RoPE, GQA attention (full / sliding /
cross / decode), SwiGLU MLP.

All functions are pure; parameters are plain dict pytrees. Attention for long
sequences uses an online-softmax KV-block scan ("flash" formulation in XLA)
so the lowered HLO never materialises an S x S score matrix. The Pallas TPU
kernels in ``repro.kernels`` implement the same math for the hot paths and are
validated against these (and ``kernels/ref.py``) in interpret mode.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x, scale=None, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    return x.astype(dt)


def nonparametric_layer_norm(x, eps=1e-5):
    """OLMo-style LayerNorm without learned scale/bias."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def make_norm(cfg, d, key=None):
    """Returns (params, apply_fn-compatible) norm parameters."""
    if cfg.nonparametric_ln:
        return {}
    return {"scale": jnp.ones((d,), dtype=cfg.dtype)}


def apply_norm(cfg, p, x):
    if cfg.nonparametric_ln:
        return nonparametric_layer_norm(x)
    return rms_norm(x, p["scale"])


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_tables(positions, head_dim, theta):
    """positions [S] -> cos/sin [S, head_dim//2] (float32)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, S, H, hd]; cos/sin [S, hd//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def apply_rope_rows(x, cos, sin):
    """x [B, 1, H, hd]; cos/sin [B, hd//2] — one angle per batch row.

    The per-slot decode path: each cache slot sits at its own position, so
    the rotation varies along batch instead of sequence."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, None, None, :].astype(x.dtype)
    s = sin[:, None, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------------------
# Attention (online-softmax KV-block scan)
# --------------------------------------------------------------------------
def _blockify(x, block):
    """[B, S, H, hd] -> [nb, B, block, H, hd] (zero-padded)."""
    B, S, H, hd = x.shape
    nb = -(-S // block)
    pad = nb * block - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(B, nb, block, H, hd).transpose(1, 0, 2, 3, 4)


def _block_mask(qpos, kpos, Sk, causal, window):
    valid = kpos[None, :] < Sk
    if causal:
        valid = valid & (kpos[None, :] <= qpos[:, None])
    if window:
        valid = valid & (kpos[None, :] > qpos[:, None] - window)
    return valid


def flash_attention_xla(q, k, v, *, causal=True, window=0, q_offset=0,
                        block=1024):
    """Keyword-friendly wrapper over the custom-VJP core."""
    return _flash_core(q, k, v, causal, window, q_offset, block)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, causal=True, window=0, q_offset=0,
                block=1024):
    """q [B,Sq,H,hd]; k,v [B,Sk,KV,hd]. GQA via head repeat.

    XLA analogue of FlashAttention with a *custom VJP*: the forward scans KV
    blocks carrying fp32 (max, denom, acc); the backward recomputes block
    scores instead of saving them, so residuals are O(S*d) — without this,
    the scan's saved exp(s-m) residuals are [nb, B, H, Sq, block] and blow
    past HBM at 4k-32k sequence lengths (EXPERIMENTS.md §Perf, iteration 0).
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, block)
    return out


def _flash_fwd_impl(q, k, v, causal, window, q_offset, block):
    # GQA via *grouped einsums*: q is viewed as [B, Sq, KV, rep, hd] and
    # contracted against the un-repeated KV tensors — a materialised
    # jnp.repeat of K/V forced an all-gather + rep x HBM traffic under SPMD
    # (EXPERIMENTS.md §Perf, qwen3-4b iterations).
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = hd ** -0.5
    block = min(block, Sk)
    nb = -(-Sk // block)
    kb = constrain(_blockify(k, block),
                   (None, "batch", None, "kv_heads", None))
    vb = constrain(_blockify(v, block),
                   (None, "batch", None, "kv_heads", None))
    qpos = q_offset + jnp.arange(Sq)
    qs = q.reshape(B, Sq, KV, rep, hd) * scale
    qs = constrain(qs, ("batch", None, "kv_heads", None, None))

    def body(carry, inp):
        m, l, acc = carry                              # [B,KV,rep,Sq(,hd)]
        kblk, vblk, bidx = inp                         # [B,block,KV,hd]
        kpos = bidx * block + jnp.arange(block)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qs, kblk,
                       preferred_element_type=jnp.float32)
        valid = _block_mask(qpos, kpos, Sk, causal, window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = constrain(jnp.full((B, KV, rep, Sq), NEG_INF, jnp.float32),
                   ("batch", "kv_heads", None, None))
    l0 = constrain(jnp.zeros((B, KV, rep, Sq), jnp.float32),
                   ("batch", "kv_heads", None, None))
    a0 = constrain(jnp.zeros((B, KV, rep, Sq, hd), jnp.float32),
                   ("batch", "kv_heads", None, None, None))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(
        0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))           # [B,KV,rep,Sq]
    return out, lse


def _flash_fwd(q, k, v, causal, window, q_offset, block):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, block)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, block, res, dout):
    q, k, v, out, lse = res                   # lse [B,KV,rep,Sq]
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = hd ** -0.5
    blk = min(block, Sk)
    nb = -(-Sk // blk)
    kb = constrain(_blockify(k, blk), (None, "batch", None, "kv_heads",
                                       None))
    vb = constrain(_blockify(v, blk), (None, "batch", None, "kv_heads",
                                       None))
    qpos = q_offset + jnp.arange(Sq)
    qs = constrain(q.reshape(B, Sq, KV, rep, hd) * scale,
                   ("batch", None, "kv_heads", None, None))
    do = constrain(
        dout.reshape(B, Sq, KV, rep, hd).transpose(0, 2, 3, 1, 4)
        .astype(jnp.float32),
        ("batch", "kv_heads", None, None, None))          # [B,KV,rep,Sq,hd]
    o32 = out.reshape(B, Sq, KV, rep, hd).transpose(0, 2, 3, 1, 4) \
        .astype(jnp.float32)
    delta = (do * o32).sum(-1)                            # [B,KV,rep,Sq]

    def body(dq, inp):
        kblk, vblk, bidx = inp                            # [B,blk,KV,hd]
        kpos = bidx * blk + jnp.arange(blk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qs, kblk,
                       preferred_element_type=jnp.float32)
        valid = _block_mask(qpos, kpos, Sk, causal, window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                   # [B,KV,rep,Sq,bk]
        dv = jnp.einsum("bgrqk,bgrqd->bkgd", p, do)       # sums over rep
        dp = jnp.einsum("bgrqd,bkgd->bgrqk", do, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bgrqk,bkgd->bqgrd", ds, kblk,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bgrqk,bqgrd->bkgd", ds, qs,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = constrain(jnp.zeros((B, Sq, KV, rep, hd), jnp.float32),
                    ("batch", None, "kv_heads", None, None))
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nb)))
    dq = (dq * scale).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, nb * blk, KV, hd)[:, :Sk]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, nb * blk, KV, hd)[:, :Sk]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def attention_decode_xla(q, k_cache, v_cache, pos, *, window=0):
    """Single-token decode attention. q [B,1,H,hd]; caches [B,S,KV,hd];
    pos [] current position (number of valid cached tokens is pos+1), or
    [B] per-row positions for slot-batched decode (each batch row is an
    independent stream at its own position).

    With a sliding window the cache is a ring buffer of size ``window``; the
    mask then covers every slot already written.
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    # The cache shards its *sequence* dim over whatever mesh axes the batch
    # doesn't use (decode_32k: batch->data, seq->model; long_500k B=1:
    # seq->model+data). kv-heads (often < axis size) stay local and GQA is a
    # grouped einsum — no repeated KV, no all-gather of the cache.
    k_cache = constrain(k_cache, ("batch", "seq", "kv_heads", None))
    v_cache = constrain(v_cache, ("batch", "seq", "kv_heads", None))
    scale = hd ** -0.5
    qg = q[:, 0].reshape(B, KV, rep, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg * scale, k_cache,
                   preferred_element_type=jnp.float32)     # [B,KV,rep,S]
    kpos = jnp.arange(S)
    if jnp.ndim(pos):                               # per-row positions [B]
        if window:
            valid = kpos[None, :] < jnp.minimum(pos + 1, S)[:, None]
        else:
            valid = kpos[None, :] <= pos[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    else:
        if window:
            valid = kpos < jnp.minimum(pos + 1, S)  # ring buffer: slots written
        else:
            valid = kpos <= pos
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# Attention layer (params + forward)
# --------------------------------------------------------------------------
def init_attention(cfg, key, cross=False):
    D, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = cfg.dtype

    def dense(k, shape):
        fan_in = shape[0]
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dt)

    p = {
        "wq": dense(ks[0], (D, H * hd)),
        "wk": dense(ks[1], (D, KV * hd)),
        "wv": dense(ks[2], (D, KV * hd)),
        "wo": dense(ks[3], (H * hd, D)),
        "norm": make_norm(cfg, D),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    if cross:
        p["cross_norm"] = make_norm(cfg, D)
    return p


def _qkv(cfg, p, xq, xkv):
    B, Sq, D = xq.shape
    Skv = xkv.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Sq, H, hd)
    k = k.reshape(B, Skv, KV, hd)
    v = v.reshape(B, Skv, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def self_attention_fwd(cfg, p, x, rope_cs, *, window=0, q_offset=0,
                       backend=None):
    """Full/causal self attention for train & prefill. Returns (out, (k, v)).

    ``backend`` overrides ``cfg.attn_backend``: "pallas" routes through the
    Pallas flash-attention kernel where it covers the case (causal,
    q_offset == 0); otherwise — and always for "jnp" — the XLA
    online-softmax path runs."""
    q, k, v = _qkv(cfg, p, x, x)
    cos, sin = rope_cs
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    backend = backend or getattr(cfg, "attn_backend", "jnp")
    if backend == "pallas" and not q_offset:
        from repro.kernels import ops as kernel_ops
        o = kernel_ops.flash_attention_op(q, k, v, causal=True, window=window)
    else:
        o = flash_attention_xla(q, k, v, causal=True, window=window,
                                q_offset=q_offset)
    B, S, H, hd = o.shape
    return o.reshape(B, S, H * hd) @ p["wo"], (k, v)


def cross_attention_fwd(cfg, p, x, kv_or_embeds, *, from_cache=False):
    """Cross attention to modality embeddings. Returns (out, (k, v))."""
    if from_cache:
        q = x @ p["wq"]
        if cfg.qkv_bias:
            q = q + p["bq"]
        B, Sq, _ = x.shape
        H, hd = cfg.n_heads, cfg.resolved_head_dim
        q = q.reshape(B, Sq, H, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
        k, v = kv_or_embeds
    else:
        q, k, v = _qkv(cfg, p, x, kv_or_embeds)
    o = flash_attention_xla(q, k, v, causal=False)
    B, Sq, H, hd = o.shape
    return o.reshape(B, Sq, H * hd) @ p["wo"], (k, v)


def self_attention_decode(cfg, p, x, cache, pos, rope_cs, *, window=0,
                          backend=None):
    """One-token decode. x [B,1,D]; cache {'k','v'} ring buffers.

    ``pos`` is scalar (whole batch at one position) or [B] (slot-batched
    streams, each at its own position — ``rope_cs`` then holds per-row
    tables [B, hd//2]).  ``backend`` as in :func:`self_attention_fwd`.

    Returns (out, new_rows): the step's own K/V rows {'k','v'} [B,1,KV,hd],
    not the cache — the caller writes them into its pool (one row per
    stream), so the pool can be updated in place."""
    q, k, v = _qkv(cfg, p, x, x)
    cos, sin = rope_cs            # tables for the single position, [1, hd//2]
    vector = bool(jnp.ndim(pos))
    if vector:
        q = apply_rope_rows(q, cos, sin)
        k = apply_rope_rows(k, cos, sin)
    else:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    S = cache["k"].shape[1]
    slot = (pos % S) if window else pos
    # The cache attention reads: the pool with this step's row in place.
    # The jnp path fuses it into the attention read; it is never stored.
    if vector:
        # jnp.where keeps untouched rows bit-identical (no arithmetic on
        # them), which the slot-isolation guarantee of the
        # continuous-batching engine relies on.
        sel = (jnp.arange(S)[None, :] == slot[:, None])[:, :, None, None]
        k_cache = jnp.where(sel, k, cache["k"])
        v_cache = jnp.where(sel, v, cache["v"])
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                      axis=1)
    backend = backend or getattr(cfg, "attn_backend", "jnp")
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        o = kernel_ops.decode_attention_op(q[:, 0], k_cache, v_cache, pos)
        o = o[:, None].astype(q.dtype)
    else:
        o = attention_decode_xla(q, k_cache, v_cache, pos, window=window)
    B, _, H, hd = o.shape
    out = o.reshape(B, 1, H * hd) @ p["wo"]
    return out, {"k": k, "v": v}


def init_attn_cache(cfg, batch, seq_len, cross_len=0):
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    dt = cfg.dtype
    return {"k": jnp.zeros((batch, S, KV, hd), dt),
            "v": jnp.zeros((batch, S, KV, hd), dt)}


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def init_mlp(cfg, key, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.dtype

    def dense(k, shape):
        return (jax.random.normal(k, shape) / math.sqrt(shape[0])).astype(dt)

    p = {"w1": dense(ks[0], (D, F)), "w2": dense(ks[1], (F, D)),
         "norm": make_norm(cfg, D)}
    if cfg.act == "silu":                 # SwiGLU
        p["w3"] = dense(ks[2], (D, F))
    return p


def mlp_fwd(cfg, p, x):
    h = x @ p["w1"]
    if cfg.act == "silu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    elif cfg.act == "relu":
        h = jax.nn.relu(h)
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h)
    return h @ p["w2"]
