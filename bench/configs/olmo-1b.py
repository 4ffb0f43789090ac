"""OLMo-1B (arXiv:2402.00838) as the benchmark runs it.

Sizes come from ``olmo-1b.json`` beside this file.  This module makes the
weights from the seed, hands them to the program's coded LM deployment, and
holds the plain reference and the work counts that the metrics use.  The
reference imports nothing of the program: it is the published architecture
written out in ``jax.numpy`` and float32 — pre-norm blocks with LayerNorm
without parameters (eps 1e-5), rotary embeddings on the two halves of each
head (theta 10000), causal softmax attention, a SwiGLU MLP
(``silu(h W1) * (h W3) W2``) and an output head tied to the embedding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------- weights ---
def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit seeds included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _shapes(m):
    D, H, KV, hd, F, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                             m["head_dim"], m["d_ff"], m["vocab"],
                             m["n_layers"])
    return {"wq": (L, D, H * hd), "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
            "wo": (L, H * hd, D), "w1": (L, D, F), "w3": (L, D, F),
            "w2": (L, F, D)}


def init_weights(cfg, seed: int):
    """Every weight, made on the device in one jitted call, in the dtype it
    is served in and in the program's parameter layout (one stacked block
    per layer group)."""
    m = cfg["model"]
    dt = jnp.dtype(m["dtype"])
    shapes = _shapes(m)

    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        w = {n: (jax.random.normal(k, s, jnp.float32)
                 / math.sqrt(s[1])).astype(dt)
             for k, (n, s) in zip(keys[1:], shapes.items())}
        embed = (jax.random.normal(keys[0], (m["vocab"], m["d_model"]),
                                   jnp.float32) * 0.02).astype(dt)
        block = {"attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                 "mlp": {n: w[n] for n in ("w1", "w2", "w3")}}
        block["attn"]["norm"] = {}
        block["mlp"]["norm"] = {}
        return {"embed": embed, "blocks": (block,), "final_norm": {}}
    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


# ----------------------------------------------------------- deployment ---
def arch(cfg):
    from repro.configs.base import ArchConfig
    m, d = cfg["model"], cfg["deployment"]
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], rope_theta=m["rope_theta"], nonparametric_ln=True,
        tie_embeddings=True, act="silu", attn_backend=d["attn_backend"],
        dtype=m["dtype"], source=cfg["source"])


def instances(cfg):
    """Instance ids of the deployment's members and parities."""
    from repro.serving.scenarios import instance_id
    d = cfg["deployment"]
    return ([instance_id("main", i) for i in range(d["k"])]
            + [instance_id(f"parity{j}", 0) for j in range(d["r"])])


def deploy(cfg, params, delay_fn):
    """The coded LM deployment through the program's normal entry point."""
    from repro.serving.api import BatchingPolicy, deploy_lm
    from repro.serving.generation import GenerationSpec
    d = cfg["deployment"]
    if d["parity_params"] != "deployed":
        raise ValueError("only parity_params='deployed' is supported")
    spec = GenerationSpec(
        cfg=arch(cfg), params=params, scheme=d["scheme"], k=d["k"], r=d["r"],
        batching=BatchingPolicy(max_size=d["slots"]),
        max_seq_len=d["pool_positions"], straggle_ms=d["straggle_ms"],
        delay_fn=delay_fn)
    return deploy_lm(spec, engine=d["engine"])


def code_coeffs(cfg):
    """The [r, k] coefficients of the deployment's code (the sum code's
    Vandermonde rows, C[j, i] = (i + 1) ** j), written out here so the
    reference's decode does not read the program's."""
    d = cfg["deployment"]
    if d["scheme"] != "sum":
        raise ValueError(f"reference decode knows the sum code only, "
                         f"not {d['scheme']!r}")
    return np.array([[(i + 1) ** j for i in range(d["k"])]
                     for j in range(d["r"])], np.float64)


# ------------------------------------------------------------ reference ---
def _layer_norm(x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x [S, heads, hd]: rotate the two halves of each head by position."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _quant_int8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (the lower-precision control), returned dequantized in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(a, w, int8):
    """a [S, in] @ w [in, out] in float32; ``int8``: both operands rounded
    to int8 first, per row of ``a`` and per output column of ``w``."""
    if int8:
        a, w = _quant_int8(a, -1), _quant_int8(w, 0)
    return a @ w


def reference_logits(cfg, weights, x, *, int8=False):
    """Logits [S, V] of a plain float32 forward over one sequence.

    ``x``: token ids [S] (int) or input embeddings [S, D] (float).
    ``weights`` are the served weights (any float dtype), widened to float32
    layer by layer.  ``int8=True`` is the control: every projection and the
    output head computed from int8-rounded operands.  Runs under "highest"
    matmul precision, so a TPU does not round float32 products to bf16."""
    m = cfg["model"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    embed = weights["embed"].astype(jnp.float32)
    if jnp.issubdtype(x.dtype, jnp.integer):
        h = embed[x]
    else:
        h = x.astype(jnp.float32)
    S = h.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    blk = weights["blocks"][0]
    stacked = {**{n: blk["attn"][n] for n in ("wq", "wk", "wv", "wo")},
               **{n: blk["mlp"][n] for n in ("w1", "w2", "w3")}}

    def layer(h, w):
        w = {n: v.astype(jnp.float32) for n, v in w.items()}
        a = _layer_norm(h)
        q = _rope(_matmul(a, w["wq"], int8).reshape(S, H, hd),
                  m["rope_theta"])
        k = _rope(_matmul(a, w["wk"], int8).reshape(S, KV, hd),
                  m["rope_theta"])
        v = _matmul(a, w["wv"], int8).reshape(S, KV, hd)
        rep = H // KV
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
        h = h + _matmul(o, w["wo"], int8)
        a = _layer_norm(h)
        g = jax.nn.silu(_matmul(a, w["w1"], int8)) * _matmul(a, w["w3"], int8)
        return h + _matmul(g, w["w2"], int8), None

    with jax.default_matmul_precision("highest"):
        h, _ = jax.lax.scan(layer, h, stacked)
        return _matmul(_layer_norm(h), embed.T, int8)


def reference_embeds(weights, tokens):
    """Float32 input embeddings of token ids [S] -> [S, D]."""
    return weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)


# --------------------------------------------------------- work counts ---
def _per_token(m):
    """(weight FLOPs per token without the output head, head FLOPs,
    attention FLOPs per attended position, cache bytes per position)."""
    D, H, KV, hd, F, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                             m["head_dim"], m["d_ff"], m["vocab"],
                             m["n_layers"])
    proj = L * (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F)
    itemsize = jnp.dtype(m["dtype"]).itemsize
    return (2 * proj, 2 * D * V, 4 * L * H * hd,
            2 * L * KV * hd * itemsize)


def weight_bytes(cfg):
    m = cfg["model"]
    n = sum(math.prod(s) for s in _shapes(m).values()) \
        + m["vocab"] * m["d_model"]
    return n * jnp.dtype(m["dtype"]).itemsize


def decode_work(cfg, positions):
    """(FLOPs, bytes) one decode step must do for streams whose caches hold
    ``positions`` tokens each: every weight read once, each stream's cache
    read over its occupied positions and one new entry written."""
    proj, head, attn, kv = _per_token(cfg["model"])
    pos = np.asarray(positions, np.float64)
    flops = len(pos) * (proj + head) + attn * float((pos + 1).sum())
    nbytes = weight_bytes(cfg) + kv * float(pos.sum() + len(pos))
    return flops, nbytes


def prefill_work(cfg, length):
    """(FLOPs, bytes) of a prefill over ``length`` positions that projects
    only its last position to logits: causal attention over the prefix,
    every weight read once, the cache written."""
    proj, head, attn, kv = _per_token(cfg["model"])
    flops = length * proj + head + attn * length * (length + 1) / 2
    return flops, weight_bytes(cfg) + kv * length


def token_flops(cfg, context):
    """FLOPs of producing one output token with ``context`` tokens already
    in the cache."""
    proj, head, attn, _ = _per_token(cfg["model"])
    return proj + head + attn * (context + 1)
