"""Composable model assembly for all assigned architecture families.

A model is a stack of ``n_layers`` layers with a repeating *superblock* of
length ``cfg.period`` (1 for uniform stacks; 8 for Jamba's 1-attn:7-mamba
interleave; 5 for Llama-vision's cross-attn insertion; ...). Parameters for
the superblocks are stacked along a leading "group" axis and the stack is
executed with ``lax.scan`` (+ optional remat), which keeps compiled HLO size
independent of depth — essential for 94-layer dry-runs on the 512-device mesh.

Three entry points per model:
  * ``forward``      — full-sequence teacher-forced logits (training)
  * ``prefill``      — full-sequence + returns per-layer KV/SSM caches
  * ``decode_step``  — one token through the cached stack (serving decode)
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE


# --------------------------------------------------------------------------
# Layer plan
# --------------------------------------------------------------------------
def layer_plan(cfg, role="decoder"):
    """Tuple of per-layer specs for one superblock period."""
    plan = []
    for i in range(cfg.period):
        if role == "encoder":
            plan.append({"mixer": "attn", "cross": False, "ffn": "mlp",
                         "causal": False})
            continue
        if cfg.attn_every:                       # hybrid (jamba)
            mixer = "attn" if i == cfg.attn_every // 2 else "mamba"
        elif cfg.family == "ssm":
            mixer = "mamba"
        elif cfg.cross_attn_every and i == cfg.cross_attn_every - 1:
            mixer = "none"                       # VLM cross-attn layer
        else:
            mixer = "attn"
        cross = bool(cfg.cross_attn_every and i == cfg.cross_attn_every - 1)
        if cfg.enc_dec and role == "decoder":
            cross = True
        if cfg.family == "ssm":
            ffn = "none"
        elif cfg.n_experts and (i % cfg.moe_every == cfg.moe_every - 1):
            ffn = "moe"
        else:
            ffn = "mlp"
        plan.append({"mixer": mixer, "cross": cross, "ffn": ffn,
                     "causal": True})
    return tuple(plan)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def init_layer(cfg, key, spec):
    ks = jax.random.split(key, 4)
    p = {}
    if spec["mixer"] == "attn":
        p["attn"] = L.init_attention(cfg, ks[0])
    elif spec["mixer"] == "mamba":
        p["mamba"] = M.init_mamba(cfg, ks[0])
    if spec["cross"]:
        p["cross"] = L.init_attention(cfg, ks[1], cross=True)
    if spec["ffn"] == "mlp":
        p["mlp"] = L.init_mlp(cfg, ks[2])
    elif spec["ffn"] == "moe":
        p["moe"] = MOE.init_moe(cfg, ks[2])
    return p


def _init_stack(cfg, key, n_groups, plan):
    def one_group(k):
        kl = jax.random.split(k, len(plan))
        return tuple(init_layer(cfg, kl[i], plan[i])
                     for i in range(len(plan)))
    return jax.vmap(one_group)(jax.random.split(key, n_groups))


def init_params(cfg, key):
    ks = jax.random.split(key, 5)
    dt = cfg.dtype
    D, V = cfg.d_model, cfg.vocab
    p = {
        "embed": (jax.random.normal(ks[0], (V, D)) * 0.02).astype(dt),
        "blocks": _init_stack(cfg, ks[1], cfg.n_groups, layer_plan(cfg)),
        "final_norm": L.make_norm(cfg, D),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[2], (D, V))
                        / math.sqrt(D)).astype(dt)
    if cfg.enc_dec:
        assert cfg.n_enc_layers % cfg.period == 0
        p["encoder"] = {
            "blocks": _init_stack(cfg, ks[3], cfg.n_enc_layers // cfg.period,
                                  layer_plan(cfg, role="encoder")),
            "final_norm": L.make_norm(cfg, D),
        }
    return p


# --------------------------------------------------------------------------
# Single layer forward
# --------------------------------------------------------------------------
def _layer_fwd(cfg, spec, p, x, ctx):
    """Full-sequence layer. Returns (x, aux, cache_entry)."""
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    if spec["mixer"] == "attn":
        h = L.apply_norm(cfg, p["attn"]["norm"], x)
        o, (k, v) = L.self_attention_fwd(
            cfg, p["attn"], h, ctx["rope"], window=ctx["window"]) \
            if spec["causal"] else _bidir_attn(cfg, p["attn"], h, ctx)
        x = x + o
        if ctx["collect_cache"]:
            W = ctx["window"]
            if W and k.shape[1] > W:
                k, v = k[:, -W:], v[:, -W:]
            pad = ctx["cache_len"] - k.shape[1]
            if pad > 0:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cache["attn"] = {"k": k, "v": v}
    elif spec["mixer"] == "mamba":
        h = L.apply_norm(cfg, p["mamba"]["norm"], x)
        o, state = M.ssd_fwd(cfg, p["mamba"], h,
                             return_state=ctx["collect_cache"])
        x = x + o
        if ctx["collect_cache"]:
            cache["ssm"] = state
    if spec["cross"]:
        h = L.apply_norm(cfg, p["cross"]["cross_norm"], x)
        o, (ck, cv) = L.cross_attention_fwd(cfg, p["cross"], h,
                                            ctx["cross_embeds"])
        x = x + o
        if ctx["collect_cache"]:
            cache["cross"] = {"k": ck, "v": cv}
    if spec["ffn"] == "mlp":
        h = L.apply_norm(cfg, p["mlp"]["norm"], x)
        x = x + L.mlp_fwd(cfg, p["mlp"], h)
    elif spec["ffn"] == "moe":
        h = L.apply_norm(cfg, p["moe"]["norm"], x)
        o, a = MOE.moe_fwd(cfg, p["moe"], h)
        x = x + o
        aux = aux + a
    return x, aux, cache


def _bidir_attn(cfg, p, h, ctx):
    q, k, v = L._qkv(cfg, p, h, h)
    cos, sin = ctx["rope"]
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    o = L.flash_attention_xla(q, k, v, causal=False)
    B, S, H, hd = o.shape
    return o.reshape(B, S, H * hd) @ p["wo"], (k, v)


def _layer_decode(cfg, spec, p, x, lcache, pos, ctx):
    """One-token layer step. Returns (x, update): an attention layer's new
    K/V rows [B,1,KV,hd] and an SSM layer's whole state; a cross-attention
    cache is read only, so it is left out."""
    new = {}
    if spec["mixer"] == "attn":
        h = L.apply_norm(cfg, p["attn"]["norm"], x)
        o, kv = L.self_attention_decode(cfg, p["attn"], h, lcache["attn"],
                                        pos, ctx["rope"],
                                        window=ctx["window"])
        x = x + o
        new["attn"] = kv
    elif spec["mixer"] == "mamba":
        h = L.apply_norm(cfg, p["mamba"]["norm"], x)
        o, st = M.ssd_decode(cfg, p["mamba"], h, lcache["ssm"])
        x = x + o
        new["ssm"] = st
    if spec["cross"]:
        h = L.apply_norm(cfg, p["cross"]["cross_norm"], x)
        ck, cv = lcache["cross"]["k"], lcache["cross"]["v"]
        o, _ = L.cross_attention_fwd(cfg, p["cross"], h, (ck, cv),
                                     from_cache=True)
        x = x + o
    if spec["ffn"] == "mlp":
        h = L.apply_norm(cfg, p["mlp"]["norm"], x)
        x = x + L.mlp_fwd(cfg, p["mlp"], h)
    elif spec["ffn"] == "moe":
        h = L.apply_norm(cfg, p["moe"]["norm"], x)
        o, _ = MOE.moe_fwd(cfg, p["moe"], h)
        x = x + o
    return x, new


# --------------------------------------------------------------------------
# Stack (scan over superblocks)
# --------------------------------------------------------------------------
def _stack_fwd(cfg, stacked, x, ctx, plan, remat=False):
    def body(carry, gp):
        x, aux = carry
        x = constrain(x, ("batch", None, None))
        caches = []
        for i, spec in enumerate(plan):
            x, a, c = _layer_fwd(cfg, spec, gp[i], x, ctx)
            aux = aux + a
            caches.append(c)
        return (constrain(x, ("batch", None, None)), aux), tuple(caches)

    if remat:
        body = jax.checkpoint(body)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    stacked)
    return x, aux, caches


def _write_rows(pool, rows, pos, window):
    """Write each stream's new row into a stacked pool: pool [G,B,S,KV,hd],
    rows [G,B,1,KV,hd], row b at slot ``pos[b]`` (``pos % S`` under a
    sliding window).  A scatter of B rows a layer: with the pool donated,
    XLA updates it in place."""
    B, S = pool.shape[1], pool.shape[2]
    slot = jnp.broadcast_to(pos % S if window else pos, (B,))
    return pool.at[:, jnp.arange(B), slot].set(
        rows[:, :, 0], indices_are_sorted=True, unique_indices=True)


def _stack_decode(cfg, stacked, caches, x, pos, ctx, plan):
    """The scan emits each layer's update, not its cache (``_layer_decode``);
    after it, attention rows are written into their pools, SSM states
    replace theirs and cross-attention caches pass through as given."""
    def body(x, inp):
        gp, gc = inp
        new = []
        for i, spec in enumerate(plan):
            x, c = _layer_decode(cfg, spec, gp[i], x, gc[i], pos, ctx)
            new.append(c)
        return x, tuple(new)

    x, updates = jax.lax.scan(body, x, (stacked, caches))
    new_caches = []
    for old, upd in zip(caches, updates):
        new = {**old, **upd}
        if "attn" in upd:
            new["attn"] = {n: _write_rows(old["attn"][n], upd["attn"][n], pos,
                                          ctx["window"]) for n in ("k", "v")}
        new_caches.append(new)
    return x, tuple(new_caches)


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------
def _embed(cfg, params, tokens=None, embeds=None):
    if embeds is not None:
        return embeds.astype(cfg.dtype)
    return params["embed"][tokens]


def embed_tokens(cfg, params, tokens):
    """Public: token -> embedding (used by the ParM embedding-space encoder)."""
    return params["embed"][tokens]


def _logits(cfg, params, x, logits_pspec=None):
    x = L.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out = (x @ head).astype(jnp.float32)
    # keep the fp32 logits vocab-sharded on the tensor axis — unsharded
    # [B*S, V] fp32 logits dominate train-step HBM otherwise
    out = constrain(out, ("batch", None, "vocab"))
    if logits_pspec is not None:
        out = jax.lax.with_sharding_constraint(out, logits_pspec)
    return out


def _make_ctx(cfg, S, *, q_offset=0, cross_embeds=None, collect_cache=False,
              cache_len=0):
    pos = q_offset + jnp.arange(S)
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    rope = (L.rope_tables(pos, hd, cfg.rope_theta) if hd else (None, None))
    return {"rope": rope, "window": cfg.sliding_window,
            "cross_embeds": cross_embeds, "collect_cache": collect_cache,
            "cache_len": cache_len}


def run_encoder(cfg, params, frames):
    """Seamless encoder over stubbed frame embeddings [B, S_src, D]."""
    ctx = _make_ctx(cfg, frames.shape[1])
    plan = layer_plan(cfg, role="encoder")
    x = frames.astype(cfg.dtype)
    x, _, _ = _stack_fwd(cfg, params["encoder"]["blocks"], x, ctx, plan)
    return L.apply_norm(cfg, params["encoder"]["final_norm"], x)


def forward(cfg, params, tokens=None, embeds=None, cross_embeds=None,
            remat=False, logits_pspec=None, unembed_last_only=False):
    """Teacher-forced full-sequence logits. Returns (logits_f32, aux).

    ``unembed_last_only`` skips the [B, S, V] unembed and projects only the
    final position — the serving prefill only consumes the last token."""
    if cfg.enc_dec:
        cross_embeds = run_encoder(cfg, params, cross_embeds)
    x = _embed(cfg, params, tokens, embeds)
    ctx = _make_ctx(cfg, x.shape[1], cross_embeds=cross_embeds)
    x, aux, _ = _stack_fwd(cfg, params["blocks"], x, ctx, layer_plan(cfg),
                           remat=remat)
    if unembed_last_only:
        x = x[:, -1:]
    return _logits(cfg, params, x, logits_pspec), aux


def prefill(cfg, params, tokens=None, embeds=None, cross_embeds=None,
            cache_len=0):
    """Process the prompt; returns (last-token logits_f32, cache).

    ``cache_len`` reserves decode slots (>= prompt length, or == window for
    sliding-window archs)."""
    if cfg.enc_dec:
        cross_embeds = run_encoder(cfg, params, cross_embeds)
    x = _embed(cfg, params, tokens, embeds)
    S = x.shape[1]
    if not cache_len:
        cache_len = min(S, cfg.sliding_window) if cfg.sliding_window else S
    ctx = _make_ctx(cfg, S, cross_embeds=cross_embeds, collect_cache=True,
                    cache_len=cache_len)
    x, aux, caches = _stack_fwd(cfg, params["blocks"], x, ctx,
                                layer_plan(cfg))
    return _logits(cfg, params, x[:, -1:]), caches


def decode_step(cfg, params, cache, pos, token=None, embed=None):
    """One decode step at position ``pos`` (0-based, == #tokens already in
    cache). ``pos`` may be a scalar (whole batch at one position) or a [B]
    vector of per-row positions — the slot-batched continuous-decoding path,
    where each batch row is an independent stream. Returns
    (logits_f32 [B,1,V], new_cache)."""
    x = _embed(cfg, params, token, embed)
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    if jnp.ndim(pos):
        # rope tables per batch row: [B, hd//2], consumed by the
        # apply_rope_rows branch inside self_attention_decode
        rope = (L.rope_tables(pos, hd, cfg.rope_theta)
                if hd else (None, None))
    else:
        rope = (L.rope_tables(jnp.full((1,), pos), hd, cfg.rope_theta)
                if hd else (None, None))
    ctx = {"rope": rope, "window": cfg.sliding_window, "cross_embeds": None,
           "collect_cache": False, "cache_len": 0}
    x, new_caches = _stack_decode(cfg, params["blocks"], cache, x, pos, ctx,
                                  layer_plan(cfg))
    return _logits(cfg, params, x), new_caches


def init_cache(cfg, batch, cache_len):
    """Zero caches for decode-only entry (dry-run decode shapes)."""
    plan = layer_plan(cfg)
    S = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len

    def one_layer(spec):
        c = {}
        if spec["mixer"] == "attn":
            c["attn"] = L.init_attn_cache(cfg, batch, S)
        elif spec["mixer"] == "mamba":
            c["ssm"] = M.init_ssm_cache(cfg, batch)
        if spec["cross"]:
            n_ctx = cfg.n_modality_tokens or 1
            KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
            c["cross"] = {"k": jnp.zeros((batch, n_ctx, KV, hd), cfg.dtype),
                          "v": jnp.zeros((batch, n_ctx, KV, hd), cfg.dtype)}
        return c

    per_group = tuple(one_layer(s) for s in plan)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_groups,) + x.shape), per_group)


def param_count(params):
    return sum(x.size for x in jax.tree.leaves(params))
