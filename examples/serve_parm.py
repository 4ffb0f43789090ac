"""End-to-end ParM serving driver (the paper-kind end-to-end example:
serve a small model with batched requests through the coded frontend).

    PYTHONPATH=src python examples/serve_parm.py [--n 120] [--k 2] [--m 4] \
        [--batch-size 4]

Trains a deployed classifier + parity model, declares the deployment once as
a ``DeploymentSpec`` and serves a request stream through
``deploy(spec, engine="threads")`` with an injected straggler instance,
reporting latency percentiles + how each prediction was completed
(model / parity-reconstruction), plus accuracy of each path.  The SAME spec
replays through the simulator: ``deploy(spec, engine="sim").replay(trace)``.
"""
import argparse
import time

import jax
import numpy as np

from repro.core.parity import train_parity_models
from repro.data.pipeline import batched, cluster_images
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import build
from repro.serving.api import BatchingPolicy, DeploymentSpec, Trace, deploy
from repro.training.loss import softmax_xent
from repro.training.optim import AdamConfig, adam_init, adam_update

IMG = (16, 16, 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=120)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--straggle-ms", type=float, default=150.0)
    ap.add_argument("--batch-size", type=int, default=1,
                    help="adaptive-batching max batch size (main pool)")
    args = ap.parse_args()
    enable_compile_cache()

    # train deployed + parity models ---------------------------------------
    x, y, tmpl = cluster_images(3000, noise=2.0, seed=0, image_shape=IMG)
    xt, yt, _ = cluster_images(args.n, noise=2.0, seed=1, templates=tmpl,
                               image_shape=IMG)
    params, fwd = build("mlp", jax.random.PRNGKey(0), image_shape=IMG)
    opt = AdamConfig(lr=1e-3)
    state = adam_init(params, opt)

    @jax.jit
    def step(p, s, xb, yb):
        loss, g = jax.value_and_grad(
            lambda p: softmax_xent(fwd(p, xb), yb))(p)
        return (*adam_update(g, s, p, opt), loss)

    for xb, yb in batched(x, y, 64, epochs=3):
        params, state, _ = step(params, state, xb, yb)
    pp, scheme = train_parity_models(
        params, fwd, lambda kk: build("mlp", kk, image_shape=IMG)[0],
        x, k=args.k, epochs=5)
    jfwd = jax.jit(fwd)

    # serve with an injected straggler --------------------------------------
    slow = {0}

    def delay(iid):
        return args.straggle_ms / 1e3 if iid in slow else 0.0

    spec = DeploymentSpec(
        fwd=jfwd, params=params, parity_params=pp[0], strategy="parm",
        scheme=scheme, k=args.k, m=args.m, delay_fn=delay,
        batching=BatchingPolicy(max_size=args.batch_size, max_delay_ms=2.0))
    with deploy(spec, engine="threads") as sess:
        t0 = time.perf_counter()
        futs = []
        for i in range(args.n):
            futs.append(sess.submit(xt[i:i + 1]))
            time.sleep(0.008)                  # ~125 qps arrival stream
        ok = sess.wait_all(timeout=120)
        wall = time.perf_counter() - t0
        assert ok, "unanswered queries!"
        stats = sess.stats()
        lat = np.array([f.latency_ms for f in futs])
        print(f"\nserved {args.n} queries in {wall:.2f}s "
              f"(m={args.m} deployed + {max(1, args.m // args.k)} parity, "
              f"instance 0 straggles {args.straggle_ms:.0f} ms)")
        print(f"latency  p50={np.percentile(lat, 50):.1f}ms "
              f"p90={np.percentile(lat, 90):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms max={lat.max():.1f}ms")
        print(f"completed_by: {stats['completed_by']}")
        if stats["mean_batch_size"] > 1:
            print(f"adaptive batching: mean batch "
                  f"{stats['mean_batch_size']:.2f} over {stats['batches']} "
                  "inference calls")
        if stats["cancellations"]:
            print(f"redundant work cancelled: {stats['cancellations']} "
                  "queued items tombstoned")
        for how in ("model", "parity"):
            sel = [f for f in futs if f.completed_by == how]
            if sel:
                acc = np.mean([np.argmax(f.result()) == yt[f.qid]
                               for f in sel])
                print(f"accuracy of '{how}' predictions: {acc:.3f} "
                      f"(n={len(sel)})")

    # the SAME spec replays through the simulator: the DES charges its
    # calibrated service-time model (not this tiny MLP's real latency), so
    # this is the 100k-query-scale view of the deployment just served
    sim = deploy(spec, engine="sim").replay(Trace(n_queries=20_000,
                                                  qps=125.0))
    print(f"\nsim replay of the same spec: {sim.summary()}")


if __name__ == "__main__":
    main()
