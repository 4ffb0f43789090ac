"""Coded autoregressive LM serving (serving/generation.py).

The exactness substrate is a running-sum linear model: the "KV cache" is
one state vector per slot, ``state += embed(token)`` per step, ``logits =
state @ W``.  Logits are linear in the input embeddings, so embedding-space
encode + logit-space decode is EXACT — a reconstructed step must emit the
same token the straggler would have, and the continuous-batching invariants
(slot isolation, batched == sequential) must hold bit-for-bit.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.api import BatchingPolicy, deploy_lm
from repro.serving.generation import (GenerationSpec, LMSimSession,
                                      token_service_ms)
from repro.serving.scenarios import instance_id

V, D = 29, 8


def _linear_substrate(seed=0):
    rng = np.random.default_rng(seed)
    emb = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(D, V)).astype(np.float32))
    params = {"embed": emb, "W": W}

    def embed_fn(p, tokens):
        return p["embed"][jnp.asarray(tokens)]

    def prefill_fn(p, tokens=None, embeds=None, cache_len=0):
        e = embeds if embeds is not None else embed_fn(p, tokens)
        state = jnp.sum(e, axis=1)                       # [B, D]
        return (state @ p["W"])[:, None], {"state": state[None]}

    def decode_fn(p, cache, pos, token=None, embed=None):
        e = embed if embed is not None else embed_fn(p, token)   # [B, 1, D]
        state = cache["state"] + e[None, :, 0]           # [1, B, D]
        return (state[0] @ p["W"])[:, None], {"state": state}

    def init_cache_fn(p, batch, cache_len):
        return {"state": jnp.zeros((1, batch, D), jnp.float32)}

    return params, dict(prefill_fn=prefill_fn, decode_fn=decode_fn,
                        embed_fn=embed_fn, init_cache_fn=init_cache_fn)


def _spec(params, fns, **kw):
    defaults = dict(params=params, k=2, r=1, scheme="sum",
                    batching=BatchingPolicy(max_size=2), max_seq_len=64,
                    max_new_tokens=5, straggle_ms=2_000.0, **fns)
    defaults.update(kw)
    return GenerationSpec(**defaults)


def _prompts(n, seed=3, lo=2, hi=9):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, V, rng.integers(lo, hi))]
            for _ in range(n)]


def _run(spec, prompts, poll=None):
    with deploy_lm(spec, engine="threads") as sess:
        futs = []
        for i, p in enumerate(prompts):
            futs.append(sess.submit(p))
            if poll:
                poll(i, futs)
        assert sess.wait_all(60.0)
        toks = [f.result(1.0) for f in futs]
        return toks, sess.stats(), futs


def _reference(params, fns, prompt, n_tokens):
    """Uncoded greedy loop straight on the substrate."""
    logits, cache = fns["prefill_fn"](params,
                                      tokens=jnp.asarray([prompt], jnp.int32))
    out = [int(np.argmax(np.asarray(logits[0, -1])))]
    for _ in range(n_tokens - 1):
        logits, cache = fns["decode_fn"](
            params, cache, None, token=jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(np.argmax(np.asarray(logits[0, 0]))))
    return out


# -------------------------------------------------------------------------
# correctness: coded serving == uncoded greedy decode
# -------------------------------------------------------------------------
def test_matches_reference_greedy_decode():
    params, fns = _linear_substrate()
    prompts = _prompts(3)
    toks, report, _ = _run(_spec(params, fns), prompts)
    for p, t in zip(prompts, toks):
        assert t == _reference(params, fns, p, 5)
    # one inter-token gap per decode step: token 0 comes from prefill
    assert report.n == 3 * (5 - 1)
    assert report.reconstructed_steps == 0


def test_reconstructed_steps_emit_the_stragglers_tokens():
    """Member 0 misses every per-step deadline; parity reconstruction must
    keep its streams flowing with the exact tokens it would have emitted."""
    params, fns = _linear_substrate()
    slow = instance_id("main", 0)

    def delay(iid):
        return 0.3 if iid == slow else 0.0

    prompts = _prompts(2)
    spec = _spec(params, fns, batching=BatchingPolicy(max_size=1),
                 straggle_ms=50.0, delay_fn=delay)
    toks, report, futs = _run(spec, prompts)
    for p, t in zip(prompts, toks):
        assert t == _reference(params, fns, p, 5)
    # request 0 landed on member 0 (members fill first): its decode steps
    # were served from parity
    assert report.reconstructed_steps > 0
    assert futs[0].reconstructed_steps > 0
    assert report.completed_by.get("parity", 0) == report.reconstructed_steps


def test_irrecoverable_step_blocks_but_stays_correct():
    """More stragglers than parities: the step must block for the straggler
    (no silent wrong answer) and still emit the right tokens."""
    params, fns = _linear_substrate()

    members = {instance_id("main", 0), instance_id("main", 1)}

    def delay(iid):                     # both members slow, parity fast
        return 0.1 if iid in members else 0.0

    prompts = _prompts(2, seed=11)
    spec = _spec(params, fns, straggle_ms=20.0, delay_fn=delay,
                 max_new_tokens=3)
    toks, report, _ = _run(spec, prompts)
    for p, t in zip(prompts, toks):
        assert t == _reference(params, fns, p, 3)
    assert report.reconstructed_steps == 0


# -------------------------------------------------------------------------
# continuous-batching invariants
# -------------------------------------------------------------------------
def test_batched_equals_sequential_bit_equal():
    """Submitting everything upfront (continuous batching) and one-at-a-time
    (sequential) must produce bit-identical token streams."""
    params, fns = _linear_substrate(seed=5)
    prompts = _prompts(5, seed=7)
    spec = _spec(params, fns)
    batched, _, _ = _run(spec, prompts)

    sequential = []
    with deploy_lm(spec, engine="threads") as sess:
        for p in prompts:
            fut = sess.submit(p)
            sequential.append(fut.result(30.0))
    assert batched == sequential


def test_mid_flight_join_does_not_perturb_resident_stream():
    """A stream that joins mid-generation must not change a resident
    stream's remaining tokens (slot isolation, bit-equal)."""
    params, fns = _linear_substrate(seed=2)
    [pa, pb] = _prompts(2, seed=13)
    spec = _spec(params, fns, max_new_tokens=8)

    solo, _, _ = _run(spec, [pa])

    with deploy_lm(spec, engine="threads") as sess:
        fa = sess.submit(pa)
        deadline = time.monotonic() + 30.0
        while len(fa.tokens_so_far) < 3:        # genuinely mid-generation
            assert time.monotonic() < deadline
            time.sleep(1e-3)
        fb = sess.submit(pb)
        a, b = fa.result(30.0), fb.result(30.0)
    assert a == solo[0]
    assert b == _reference(params, fns, pb, 8)


def test_slot_recycling_under_oversubscription():
    """9 requests through 2x2 slots: every one completes, slots recycle."""
    params, fns = _linear_substrate(seed=4)
    prompts = _prompts(9, seed=17)
    spec = _spec(params, fns, max_new_tokens=3)
    toks, report, futs = _run(spec, prompts)
    assert len(toks) == 9
    for p, t in zip(prompts, toks):
        assert t == _reference(params, fns, p, 3)
    assert sorted(f.rid for f in futs) == list(range(9))
    assert report.n == 9 * (3 - 1)


# -------------------------------------------------------------------------
# report + transformer substrate + sim engine
# -------------------------------------------------------------------------
def test_report_per_token_fields():
    params, fns = _linear_substrate()
    _, report, futs = _run(_spec(params, fns), _prompts(2))
    assert report.engine == "threads"
    assert report.tokens_per_s > 0
    assert report.inter_token_p50_ms == report.median_ms
    assert np.isfinite(report.inter_token_p999_ms)
    assert report["reconstructed_steps"] == 0       # Mapping protocol
    for f in futs:
        gaps = f.inter_token_ms
        assert len(gaps) == 5 and all(g >= 0 for g in gaps)


@pytest.mark.slow
def test_transformer_substrate_end_to_end():
    import jax
    from repro.configs.base import get_config
    from repro.models import transformer as T
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    spec = GenerationSpec(cfg=cfg, params=params, k=2, r=1, scheme="sum",
                          batching=BatchingPolicy(max_size=2),
                          max_seq_len=32, max_new_tokens=3,
                          straggle_ms=10_000.0)
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    with deploy_lm(spec, engine="threads") as sess:
        futs = [sess.submit(p) for p in prompts]
        assert sess.wait_all(120.0)
        toks = [f.result(1.0) for f in futs]
    # reference greedy loop on the raw model
    for p, t in zip(prompts, toks):
        logits, cache = T.prefill(cfg, params,
                                  tokens=jnp.asarray([p], jnp.int32),
                                  cache_len=32)
        ref = [int(np.argmax(np.asarray(logits[0, -1])))]
        pos = len(p)
        for _ in range(2):
            logits, cache = T.decode_step(
                cfg, params, cache, pos,
                token=jnp.asarray([[ref[-1]]], jnp.int32))
            ref.append(int(np.argmax(np.asarray(logits[0, 0]))))
            pos += 1
        assert t == ref


def test_transformer_decode_consumes_its_pool():
    """Every decode step of the transformer substrate donates the pool it
    is given: the previous pool is deleted once the step has run, and a
    donation XLA could not use is an error here, not a warning."""
    import warnings

    import jax
    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving import generation
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    spec = GenerationSpec(cfg=cfg, params=params, k=2, r=1, scheme="sum",
                          batching=BatchingPolicy(max_size=2),
                          max_seq_len=16, max_new_tokens=3,
                          straggle_ms=10_000.0)
    generation._transformer_substrate.cache_clear()   # compiled anew here
    given = []
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        with deploy_lm(spec, engine="threads") as sess:
            real = sess._decode

            def decode(params, cache, pos, **inp):
                given.append(cache)
                return real(params, cache, pos, **inp)
            sess._decode = decode
            fut = sess.submit([1, 2, 3])
            assert sess.wait_all(120.0)
            assert len(fut.result(1.0)) == 3
    generation._transformer_substrate.cache_clear()
    assert len(given) == 2 * (spec.k + spec.r)      # two coded decode steps
    for pool in given:
        assert all(x.is_deleted() for x in jax.tree.leaves(pool))


@pytest.mark.parametrize("scenario", ["bursty", "storm"])
def test_sim_engine_coded_beats_uncoded_tail(scenario):
    """Roofline-calibrated token-level DES on a big config: below the
    capacity knee the coded and uncoded medians match (both ~ the roofline
    step time) and coded generation's inter-token p999 beats the uncoded
    equal-resources baseline (the PR's acceptance criterion, CI-gated at
    smoke scale)."""
    from repro.configs.base import get_config
    cfg = get_config("qwen3-moe-235b-a22b")
    base = GenerationSpec(cfg=cfg, k=4, r=1, m=12, utilization=0.3,
                          kv_len=4096, tp=8, scenario=scenario)
    step_ms = token_service_ms(base)
    assert 1.0 < step_ms < 100.0                     # calibration sanity
    coded = deploy_lm(base, engine="sim").replay(n_tokens=20_000, seed=1)
    uncoded = deploy_lm(base.replace(strategy="equal_resources"),
                        engine="sim").replay(n_tokens=20_000, seed=1)
    assert coded.reconstructed_steps > 0
    assert coded.inter_token_p50_ms == pytest.approx(
        uncoded.inter_token_p50_ms, rel=0.15)        # "at the same median"
    assert coded.inter_token_p999_ms < uncoded.inter_token_p999_ms
    assert coded.tokens_per_s > 0


def test_deploy_lm_rejects_bad_engine_and_spec():
    params, fns = _linear_substrate()
    spec = _spec(params, fns)
    with pytest.raises(ValueError):
        deploy_lm(spec, engine="carrier-pigeon")
    with pytest.raises(TypeError):
        deploy_lm({"not": "a spec"})
    with pytest.raises(ValueError):
        GenerationSpec(params=params, k=0, **fns)
    with pytest.raises(RuntimeError):
        LMSimSession(spec).stats()


def test_decode_error_reaches_wait_all_promptly():
    """A decode_fn that raises stops the scheduler; wait_all, result() and
    shutdown() re-raise the original exception instead of hanging."""
    params, fns = _linear_substrate()
    armed = []

    def decode_fn(p, cache, pos, token=None, embed=None):
        if armed:                       # the construction warm-up passes
            raise RuntimeError("decode exploded")
        return fns["decode_fn"](p, cache, pos, token=token, embed=embed)

    sess = deploy_lm(_spec(params, {**fns, "decode_fn": decode_fn}))
    armed.append(True)
    futs = [sess.submit(p) for p in _prompts(3)]
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="decode exploded"):
        sess.wait_all(60.0)
    assert time.monotonic() - t0 < 2.0
    for f in futs:
        with pytest.raises(RuntimeError, match="decode exploded"):
            f.result(1.0)
    with pytest.raises(RuntimeError, match="session failed"):
        sess.submit([1, 2])
    with pytest.raises(RuntimeError, match="decode exploded"):
        sess.shutdown()
