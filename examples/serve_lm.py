"""Coded autoregressive LM serving, end to end (DESIGN.md §13).

    PYTHONPATH=src python examples/serve_lm.py [--requests 4] [--k 2] \
        [--slots 2] [--max-new 4] [--straggle-ms 120]

Deploys a tiny transformer behind ``deploy_lm(spec, engine="threads")``:
k member instances serve multi-token requests out of per-slot KV-cache
pools (continuous batching — requests join and leave at token boundaries),
while a parity instance decodes the embedding-encoded sum of the member
streams.  Member 0 is artificially straggled: every decode step it misses,
the scheduler reconstructs its logits from the parity stream and the stream
keeps emitting tokens without waiting.

The SAME deployment shape then replays through the token-level DES at a
qwen3-moe-235b roofline-calibrated service time — the big-config tail study
(coded vs uncoded equal-resources) that runs where no TPU pod is attached.
"""
import argparse

import jax

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.api import BatchingPolicy, deploy_lm
from repro.serving.generation import GenerationSpec, token_service_ms
from repro.serving.scenarios import instance_id


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--straggle-ms", type=float, default=120.0)
    ap.add_argument("--sim-tokens", type=int, default=8000)
    args = ap.parse_args()
    enable_compile_cache()

    # threads engine: real model, one deliberately slow member ------------
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    slow = instance_id("main", 0)
    spec = GenerationSpec(
        cfg=cfg, params=params, k=args.k, r=1, scheme="sum",
        batching=BatchingPolicy(max_size=args.slots), max_seq_len=32,
        max_new_tokens=args.max_new, straggle_ms=args.straggle_ms,
        delay_fn=lambda iid: 0.4 if iid == slow else 0.0)
    prompts = [[(7 * i + j) % cfg.vocab for j in range(3 + i % 3)]
               for i in range(args.requests)]
    with deploy_lm(spec, engine="threads") as sess:
        futs = [sess.submit(p) for p in prompts]
        if not sess.wait_all(300.0):
            raise SystemExit("generation did not drain")
        for f in futs:
            print(f"request {f.rid}: tokens={f.result()} "
                  f"reconstructed_steps={f.reconstructed_steps}")
        report = sess.stats()
    print(report.summary())
    print(f"threads: tokens/s={report.tokens_per_s:.1f} "
          f"inter-token p50={report.inter_token_p50_ms:.1f}ms "
          f"p999={report.inter_token_p999_ms:.1f}ms "
          f"reconstructed={report.reconstructed_steps}")
    assert report.reconstructed_steps > 0, "straggled member never coded over"

    # sim engine: big-config tail study at roofline service time ----------
    big = get_config("qwen3-moe-235b-a22b")
    lm = GenerationSpec(cfg=big, k=4, r=1, m=12, utilization=0.3,
                        kv_len=4096, tp=8, scenario="bursty")
    print(f"\nsim: qwen3-moe-235b decode step = {token_service_ms(lm):.2f}ms"
          f" (roofline, kv_len=4096, tp=8)")
    coded = deploy_lm(lm, engine="sim").replay(n_tokens=args.sim_tokens,
                                               seed=1)
    uncoded = deploy_lm(lm.replace(strategy="equal_resources"),
                        engine="sim").replay(n_tokens=args.sim_tokens,
                                             seed=1)
    print(f"sim coded:   {coded.summary()}")
    print(f"sim uncoded: {uncoded.summary()}")
    print(f"inter-token p999: coded {coded.inter_token_p999_ms:.1f}ms vs "
          f"uncoded {uncoded.inter_token_p999_ms:.1f}ms "
          f"({coded.inter_token_p999_ms / uncoded.inter_token_p999_ms:.2f}x"
          f" at {coded.inter_token_p50_ms / uncoded.inter_token_p50_ms:.2f}x"
          f" the median)")


if __name__ == "__main__":
    main()
