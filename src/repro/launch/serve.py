"""Coded-serving launcher: ParM over any assigned LM architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        [--k 2] [--m 4] [--n 60] [--straggle-ms 120]

Builds a reduced deployed LM, distills a parity LM for it (embedding-space
addition code — the ``sum`` entry of the scheme registry, DESIGN.md §2), then
serves single-sequence queries through the declarative serving API
(``deploy(DeploymentSpec(...))`` — DESIGN.md §8) with an injected straggler
instance and prints latency + completion-path statistics.  Degraded-mode
predictions are the decoder's subtraction reconstructions. The ``--strategy``
flag picks any registered ``ResilienceStrategy`` (DESIGN.md §3);
``--batch-size`` enables Clipper-style adaptive batching on the main pool.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.data.pipeline import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.api import BatchingPolicy, DeploymentSpec, deploy
from repro.serving.strategy import available_strategies
from repro.training.optim import AdamConfig, adam_init
from repro.training.train_lib import (make_parity_train_step,
                                      make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--n", type=int, default=60)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--strategy", default="parm",
                    choices=available_strategies())
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="deadline for the default_slo strategy")
    ap.add_argument("--batch-size", type=int, default=1,
                    help="adaptive-batching max batch size (main pool)")
    ap.add_argument("--batch-delay-ms", type=float, default=2.0,
                    help="max time a worker holds a batch open")
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--parity-steps", type=int, default=40)
    ap.add_argument("--straggle-ms", type=float, default=120.0)
    args = ap.parse_args()
    enable_compile_cache()
    if get_config(args.arch).enc_dec or get_config(args.arch).family == "vlm":
        print("note: modality archs serve text-side queries here; frame/"
              "patch embeddings would ride along in production")

    cfg = get_config(args.arch, reduced=True)
    if cfg.enc_dec or cfg.family == "vlm":
        cfg = cfg.replace(enc_dec=False, n_enc_layers=0, cross_attn_every=0)
    key = jax.random.PRNGKey(0)
    B, S, k = 8, args.seq, args.k

    # 1. deployed LM
    deployed = T.init_params(cfg, key)
    opt = AdamConfig(lr=3e-3)
    tstep = jax.jit(make_train_step(cfg, opt, remat=False))
    ostate = adam_init(deployed, opt)
    data = lm_batches(cfg.vocab, B, S, args.train_steps + 40, seed=0)
    for i in range(args.train_steps):
        deployed, ostate, m = tstep(
            deployed, ostate, {"tokens": jnp.asarray(data[i])[:, :S]})
    print(f"deployed {cfg.name}: loss {float(m['loss']):.3f}")

    # 2. parity LM (distillation)
    parity = T.init_params(cfg, jax.random.PRNGKey(1))
    pstep = jax.jit(make_parity_train_step(cfg, opt))
    pstate = adam_init(parity, opt)

    @jax.jit
    def make_batch(toks):
        embeds = jax.vmap(lambda t: T.embed_tokens(cfg, deployed, t))(toks)
        teacher = jax.vmap(
            lambda t: T.forward(cfg, deployed, tokens=t)[0])(toks)
        return {"embeds": embeds, "teacher": teacher}

    for i in range(args.parity_steps):
        toks = jnp.stack([
            jnp.asarray(data[(i + j) % len(data)][: B // k, :S])
            for j in range(k)])
        parity, pstate, pm = pstep(parity, pstate, make_batch(toks))
    print(f"parity model: final distill MSE {float(pm['loss']):.4f}")

    # 3. serve: queries are token sequences; frontend encodes embeddings
    @jax.jit
    def deployed_fwd(p, emb):
        return T.forward(cfg, p, embeds=emb)[0][:, -1]   # next-token logits

    def embed(tokens):
        return np.asarray(T.embed_tokens(cfg, deployed, tokens))

    slow = {0}

    def delay(iid):
        return args.straggle_ms / 1e3 if iid in slow else 0.0

    extra = {}
    if args.strategy == "default_slo":
        # Clipper baseline: a constant (uniform-logits) default prediction
        # returned at the SLO deadline
        extra = dict(slo_ms=args.slo_ms,
                     default_prediction=np.zeros((1, cfg.vocab), np.float32))
    spec = DeploymentSpec(
        fwd=deployed_fwd, params=deployed, parity_params=parity,
        strategy=args.strategy, k=k, m=args.m, delay_fn=delay,
        batching=BatchingPolicy(max_size=args.batch_size,
                                max_delay_ms=args.batch_delay_ms),
        **extra)
    with deploy(spec, engine="threads") as sess:
        rng = np.random.default_rng(0)
        futs = []
        for i in range(args.n):
            toks = jnp.asarray(data[rng.integers(len(data))][:1, :S])
            futs.append(sess.submit(embed(toks)))
            time.sleep(0.01)
        assert sess.wait_all(timeout=120), "unanswered queries"
        stats = sess.stats()
        lat = np.array([f.latency_ms for f in futs])
        fe = sess.frontend
        lay = fe.strategy.layout(args.m, k, fe.r)
        pools = f"main={lay.main}" + \
            (f" parity={lay.parity}x{fe.r}" if lay.parity else "")
        print(f"\nserved {args.n} queries via '{args.strategy}' "
              f"({pools}; instance 0 straggles {args.straggle_ms:.0f} ms)")
        print(f"latency p50={np.percentile(lat, 50):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms max={lat.max():.1f}ms")
        print(f"completed_by: {stats['completed_by']}")
        if stats["mean_batch_size"] > 1:
            print(f"batching: {stats['batches']} inference calls, "
                  f"mean batch {stats['mean_batch_size']:.2f}")
        if stats["cancellations"]:
            print(f"redundant work cancelled: "
                  f"{stats['cancelled_queries']} originals, "
                  f"{stats['cancelled_parities']} parity queries")
        recon = [f for f in futs if f.completed_by == "parity"]
        if recon:
            print(f"{len(recon)} predictions reconstructed from parity "
                  "outputs (degraded mode)")


if __name__ == "__main__":
    main()
