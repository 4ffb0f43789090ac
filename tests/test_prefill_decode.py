"""Prefill <-> decode consistency: the cache contract the coded LM serving
engine depends on.

For each layer family (attn, ssm, moe, hybrid), ``decode_step`` run
token-by-token over a sequence must reproduce the logits of a full
teacher-forced ``forward`` — and a scalar-``pos`` decode must be bit-equal
to the vector-``pos`` (slot-batched) decode at the same uniform position,
since the continuous-batching engine always drives the vector path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import layers as L
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)

ARCHS = [
    "qwen2-0.5b",              # dense attention + bias + GQA
    "mamba2-780m",             # pure ssm
    "qwen3-moe-235b-a22b",     # moe ffn
    "jamba-1.5-large-398b",    # hybrid attn/mamba + moe
]


def _cfg(arch):
    # capacity_factor bumped so the tiny reduced MoE never drops tokens —
    # same stance as test_archs_smoke
    return get_config(arch, reduced=True).replace(capacity_factor=8.0)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_token_by_token_decode_matches_forward(arch):
    cfg = _cfg(arch)
    params = T.init_params(cfg, KEY)
    B, P, N = 2, 8, 6                  # prompt length, decoded tokens
    S = P + N
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    full, _ = T.forward(cfg, params, tokens=toks)
    last, cache = T.prefill(cfg, params, tokens=toks[:, :P], cache_len=S)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, P - 1]), atol=2e-3)
    for t in range(P, S):
        logits, cache = T.decode_step(cfg, params, cache, t,
                                      token=toks[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, t]), atol=2e-3,
                                   err_msg=f"{arch} diverged at pos {t}")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-780m"])
def test_vector_pos_decode_bit_equal_to_scalar(arch):
    """decode_step(pos scalar) == decode_step(pos [B] uniform), bit-equal."""
    cfg = _cfg(arch)
    params = T.init_params(cfg, KEY)
    B, P = 2, 8
    toks = jax.random.randint(KEY, (B, P + 1), 0, cfg.vocab)
    _, cache = T.prefill(cfg, params, tokens=toks[:, :P], cache_len=P + 4)
    tok = toks[:, P:P + 1]
    log_s, cache_s = T.decode_step(cfg, params, cache, P, token=tok)
    log_v, cache_v = T.decode_step(cfg, params, cache,
                                   jnp.full((B,), P, jnp.int32), token=tok)
    np.testing.assert_array_equal(np.asarray(log_s), np.asarray(log_v))
    for a, b in zip(jax.tree.leaves(cache_s), jax.tree.leaves(cache_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vector_pos_decode_rows_independent():
    """Each row of a vector-pos decode equals its own solo decode."""
    cfg = _cfg("qwen2-0.5b")
    params = T.init_params(cfg, KEY)
    B, S = 3, 12
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    pos = jnp.array([3, 7, 10], jnp.int32)
    # build a batched cache where row b holds a prefill of toks[b, :pos[b]]
    cache = T.init_cache(cfg, B, S)
    for b in range(B):
        _, cb = T.prefill(cfg, params, tokens=toks[b:b + 1, :int(pos[b])],
                          cache_len=S)
        cache = jax.tree.map(
            lambda pool, one, b=b: pool.at[:, b:b + 1].set(one), cache, cb)
    tok = jnp.take_along_axis(toks, pos[:, None], axis=1)
    log_v, _ = T.decode_step(cfg, params, cache, pos, token=tok)
    for b in range(B):
        _, cb = T.prefill(cfg, params, tokens=toks[b:b + 1, :int(pos[b])],
                          cache_len=S)
        log_b, _ = T.decode_step(cfg, params, cb, int(pos[b]),
                                 token=tok[b:b + 1])
        np.testing.assert_allclose(np.asarray(log_v[b]), np.asarray(log_b[0]),
                                   atol=2e-4, rtol=2e-4)


def _step_rows(cfg, params, cache, pos, tok):
    """The K/V rows a vector-``pos`` decode step computes, layer by layer:
    no layer scan and no pool write."""
    x = params["embed"][tok]
    rope = L.rope_tables(pos, cfg.resolved_head_dim, cfg.rope_theta)
    ctx = {"rope": rope, "window": cfg.sliding_window}
    rows = []
    for g in range(cfg.n_groups):
        gp = jax.tree.map(lambda a: a[g], params["blocks"])
        gc = jax.tree.map(lambda a: a[g], cache)
        for i, spec in enumerate(T.layer_plan(cfg)):
            x, upd = T._layer_decode(cfg, spec, gp[i], x, gc[i], pos, ctx)
            rows.append((i, g, upd["attn"]))
    return rows


@pytest.mark.parametrize("window", [0, 8])
def test_decode_writes_one_row_per_stream(window):
    """From a random pool, a vector-``pos`` step changes only (b, slot[b])
    of every layer's K and V — slot = pos[b], or pos[b] % S under a sliding
    window — and that row holds the step's K/V; every other (row, position)
    is bit-identical."""
    cfg = _cfg("qwen2-0.5b").replace(sliding_window=window)
    params = T.init_params(cfg, KEY)
    S = 8
    pos = jnp.array([0, S - 1, 3] + ([S + 2, 3 * S - 1] if window else []),
                    jnp.int32)
    B = pos.shape[0]
    pool = T.init_cache(cfg, B, S)
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(pool)))
    pool = jax.tree.unflatten(jax.tree.structure(pool), [
        jax.random.normal(k, x.shape, x.dtype)
        for k, x in zip(keys, jax.tree.leaves(pool))])
    tok = jax.random.randint(KEY, (B, 1), 0, cfg.vocab)
    _, new = T.decode_step(cfg, params, pool, pos, token=tok)
    slot = np.asarray(pos % S if window else pos)
    written = np.zeros((B, S), bool)
    written[np.arange(B), slot] = True
    for i, g, rows in _step_rows(cfg, params, pool, pos, tok):
        for n in ("k", "v"):
            old = np.asarray(pool[i]["attn"][n][g])
            got = np.asarray(new[i]["attn"][n][g])
            np.testing.assert_array_equal(got[~written], old[~written])
            np.testing.assert_allclose(got[np.arange(B), slot],
                                       np.asarray(rows[n][:, 0]),
                                       rtol=1e-5, atol=1e-6)
