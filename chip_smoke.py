"""On-chip smoke test: the coded serving path, end to end, on a TPU.

    python chip_smoke.py [--seed 0]    # one chip: device, lm, kernels, classify
    python chip_smoke.py --chips 4     # four chips: the tensor-parallel lm only

Phases, in order; the first failure ends the run with its traceback:

* ``device``   — what JAX sees; anything but a ``tpu`` platform is refused.
* ``lm``       — OLMo-1B at its published widths and depth, random bf16
  weights from ``--seed``, served by ``deploy_lm`` as k=2 members + 1 parity
  with member 0 straggled so that decode steps are reconstructed; then
  prefill + one cached decode step against ``transformer.forward``.
* ``kernels``  — every Pallas kernel of ``kernels/ops.py`` compiled by Mosaic
  at serving widths and checked against ``kernels/ref.py``; then the lm
  path with ``attn_backend="pallas"`` against the ``jnp`` backend.
* ``classify`` — the paper's coded classification path (``deploy``) on the
  resnet18s CNN with one straggling instance.
* ``lm_sharded`` (``--chips 4`` only, and alone) — the first decode step
  tensor-parallel over a (data=1, model=4) mesh against the same weights
  on one device, then the ``lm`` phase served through that mesh.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else comes before it.  The phases are plain functions over a
``Sizes`` so that ``tests/test_chip_smoke.py`` rehearses the same control
flow on the CPU at reduced size; only ``main`` insists on the chip.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.api import (BatchingPolicy, DeploymentSpec,  # noqa: E402
                               deploy, deploy_lm)
from repro.serving.generation import (GenerationSpec,  # noqa: E402
                                      _transformer_fns,
                                      place_inference_params)
from repro.serving.scenarios import instance_id  # noqa: E402

# Logits of the cached decode path against a full forward (and of one
# backend or layout against another), as max|diff| / max|ref|.  The models
# run in bf16: every matmul output and the residual stream are rounded to 8
# significant bits, so two summation orders of the same math differ by a few
# bf16 ulps of the largest logit (one ulp is 2^-8..2^-7 of it).  0.05 allows
# about 6-12 such ulps.  A wrong position, slot or cache entry moves logits
# by O(max|ref|), far above it.
LOGITS_TOL = 0.05
# Kernels with fp32 inputs and outputs do a handful of fp32 multiply-adds per
# element; the oracle runs at "highest" matmul precision, so only fp32
# rounding separates them.
KERNEL_TOL_F32 = 1e-4
# Kernels with bf16 inputs round their output to bf16 (2^-8 relative) after
# fp32 accumulation: a few bf16 ulps of the largest output.
KERNEL_TOL_BF16 = 2e-2


@dataclass(frozen=True)
class Sizes:
    """Everything a phase sizes itself from.  The defaults are the chip run;
    ``REHEARSAL`` is the CPU-sized copy the tests use."""

    reduced: bool = False            # get_config(reduced=True)
    requests: int = 16
    prompt_lens: tuple = (128, 256, 512)
    slots: int = 8                   # streams per member instance
    max_seq_len: int = 1024
    max_new_tokens: int = 32
    straggle_ms: float = 200.0       # per-step deadline
    straggle_every: int = 8          # member 0 sleeps on every n-th job
    timeout_s: float = 600.0
    feature: int = 224 * 224 * 3     # encode / decode query width
    hidden: int = 1024               # fused encode->forward first layer
    batch: int = 8
    groups: int = 4                  # multigroup decode
    attn_seq: int = 1024             # flash / decode attention length
    classify_queries: int = 48


REHEARSAL = Sizes(reduced=True, requests=4, prompt_lens=(8,), slots=2,
                  max_seq_len=64, max_new_tokens=4, straggle_ms=50.0,
                  straggle_every=3, timeout_s=60.0, feature=1000, hidden=256,
                  batch=2, groups=2, attn_seq=128, classify_queries=8)


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def rel_err(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def check(phase, what, err, tol):
    log(phase, f"{what}: max|diff|/max|ref| = {err:.6g} (tol {tol:g})")
    if not err <= tol:
        raise AssertionError(f"{phase}: {what} off by {err:.6g} > {tol:g}")


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def straggler(iid, every, delay_s):
    """``delay_fn``: instance ``iid`` sleeps ``delay_s`` on every
    ``every``-th job it runs; every other instance runs at full speed."""
    jobs = itertools.count(1)

    def delay(i):
        if i != iid:
            return 0.0
        return delay_s if next(jobs) % every == 0 else 0.0
    return delay


# --------------------------------------------------------------- device ---
def phase_device(want_count):
    devs = jax.devices()
    d = devs[0]
    log("device", f"devices={devs}")
    log("device", f"platform={d.platform} device_kind={d.device_kind} "
                  f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"device: platform {d.platform!r} is not 'tpu'; "
                         "this smoke test runs on the chip only")
    if len(devs) < want_count:
        raise SystemExit(f"device: {want_count} chips asked for, "
                         f"{len(devs)} present")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------- lm ---
def lm_model(sz, seed):
    """OLMo-1B (published widths, or the reduced CPU copy) and its random
    bf16 weights, initialised on the default device."""
    cfg = get_config("olmo-1b", reduced=sz.reduced)
    if sz.reduced:
        cfg = cfg.replace(dtype="bfloat16")
    params = jax.jit(lambda k: T.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    return cfg, jax.block_until_ready(params)


def step_logits(spec, params, tokens):
    """Logits of prefill over ``tokens[:, :-1]`` and of one decode step on
    ``tokens[:, -1]`` through the served path's own functions: [2, V]."""
    prefill, decode, _, _ = _transformer_fns(spec)
    P = tokens.shape[1] - 1
    lp, cache = prefill(params, tokens=tokens[:, :P],
                        cache_len=spec.max_seq_len)
    ld, _ = decode(params, cache, jnp.asarray([P], jnp.int32),
                   token=tokens[:, P:])
    return np.stack([np.asarray(lp[0, -1]), np.asarray(ld[0, 0])])


def seeded_tokens(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (1, n)), jnp.int32)


def phase_lm(cfg, params, sz, seed, *, mesh=None, phase="lm"):
    delay = straggler(instance_id("main", 0), sz.straggle_every,
                      2 * sz.straggle_ms / 1e3)
    spec = GenerationSpec(
        cfg=cfg, params=params, scheme="sum", k=2, r=1,
        batching=BatchingPolicy(max_size=sz.slots),
        max_seq_len=sz.max_seq_len, max_new_tokens=sz.max_new_tokens,
        straggle_ms=sz.straggle_ms, delay_fn=delay, mesh=mesh)
    rng = np.random.default_rng(seed)
    lens = sz.prompt_lens
    prompts = [rng.integers(0, cfg.vocab, lens[i % len(lens)]).tolist()
               for i in range(sz.requests)]
    t0 = time.perf_counter()
    with deploy_lm(spec, engine="threads") as sess:
        # one request per prompt length compiles every member and parity
        # prefill shape the measured requests use, before the clock starts
        for p in prompts[:len(lens)]:
            sess.submit(p, max_new_tokens=2)
        if not sess.wait_all(sz.timeout_s):
            raise RuntimeError(f"{phase}: warm-up unfinished")
        setup_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        futs = [sess.submit(p) for p in prompts]
        if not sess.wait_all(sz.timeout_s):
            raise RuntimeError(f"{phase}: requests unfinished after "
                               f"{sz.timeout_s}s")
        serve_s = time.perf_counter() - t1
        report = sess.stats()
    for f in futs:
        if not f.done() or len(f.result()) != sz.max_new_tokens:
            raise AssertionError(f"{phase}: request {f.rid} got "
                                 f"{len(f.tokens_so_far)} tokens")
    recon = sum(f.reconstructed_steps for f in futs)
    if recon <= 0:
        raise AssertionError(f"{phase}: no decode step was reconstructed")
    gaps = np.concatenate([f.inter_token_ms[1:] for f in futs])
    n_tok = sum(len(f.result()) for f in futs)
    log(phase, f"served {len(futs)} requests, {n_tok} tokens in "
               f"{serve_s:.3f}s: tokens/s={n_tok / serve_s:.1f} "
               f"inter-token p50={np.percentile(gaps, 50):.2f}ms "
               f"p99.9={np.percentile(gaps, 99.9):.2f}ms")
    log(phase, f"reconstructed_steps={recon} (measured requests), "
               f"completed_by={report.completed_by} (all steps)")
    log(phase, f"setup_s={setup_s:.2f} (init + compile + warm-up) "
               f"serve_s={serve_s:.3f} "
               f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")

    tokens = seeded_tokens(cfg, lens[0] + 1, seed + 1)
    got = step_logits(spec, place_inference_params(params, mesh)
                      if mesh is not None else params, tokens)
    want, _ = jax.jit(lambda p, t: T.forward(cfg, p, tokens=t))(params,
                                                                 tokens)
    want = np.asarray(want[0, -2:])
    check(phase, "prefill logits vs forward", rel_err(got[0], want[0]),
          LOGITS_TOL)
    check(phase, "cached decode logits vs forward", rel_err(got[1], want[1]),
          LOGITS_TOL)


# -------------------------------------------------------------- kernels ---
def kernel_inputs(cfg, sz, seed):
    """Every kernel's random inputs, made on the device in one program."""
    f32, bf = jnp.float32, jnp.bfloat16
    k, B, F, V, G = 2, sz.batch, sz.feature, cfg.vocab, sz.groups
    H, KV, hd, S = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                    sz.attn_seq)
    normal = {"q": ((k, B, F), f32), "po": ((B, V), f32),
              "outs": ((k, B, V), f32), "gpo": ((G, B, V), f32),
              "gouts": ((G, k, B, V), f32), "h": ((4, B, F), f32),
              "w": ((4, 2), f32), "fq": ((k, B, F), bf),
              "fw": ((2, F, sz.hidden), bf), "aq": ((1, S, H, hd), bf),
              "ak": ((1, S, KV, hd), bf), "av": ((1, S, KV, hd), bf),
              "dq": ((B, H, hd), bf), "dk": ((B, S, KV, hd), bf),
              "dv": ((B, S, KV, hd), bf)}
    # code coefficients, bounded away from 0 (decode divides by them)
    coeffs = {"c": (k,), "fc": (2, k), "gc": (G, k), "bc": (2, k)}

    def draw(sample, shapes):
        # one draw cut into pieces: every random call adds its own
        # generator to the program, and a call per array tripled the
        # CPU rehearsal's compile time
        sizes = [int(np.prod(s)) for s in shapes]
        flat = sample((sum(sizes),))
        return [p.reshape(s)
                for p, s in zip(jnp.split(flat, np.cumsum(sizes)[:-1]),
                                shapes)]

    def make(key):
        kn, kc, kp = jax.random.split(key, 3)
        out = {n: x.astype(dt) for (n, (_, dt)), x in zip(
            normal.items(),
            draw(lambda s: jax.random.normal(kn, s),
                 [s for s, _ in normal.values()]))}
        out.update(zip(coeffs, draw(
            lambda s: 1.0 + jax.random.uniform(kc, s), list(coeffs.values()))))
        out["pos"] = jax.random.randint(kp, (B,), 0, S, jnp.int32)
        return out
    return jax.block_until_ready(jax.jit(make)(jax.random.PRNGKey(seed)))


def kernel_cases(cfg, sz, seed):
    """(name, op, args, oracle, oracle args, tol) for every kernel of
    ``kernels/ops.py`` at serving widths."""
    x = kernel_inputs(cfg, sz, seed)
    k, G = 2, sz.groups
    avail = x["c"] * (jnp.arange(k) != 1)
    gidx = jnp.arange(G) % k
    gcmat = jnp.concatenate(
        [x["gc"] * (jnp.arange(k)[None] != gidx[:, None]),
         1.0 / jnp.take_along_axis(x["gc"], gidx[:, None], axis=1)], axis=1)
    enc = (x["q"], x["c"])
    dec = (x["po"], x["outs"], x["c"])
    fused = (x["fq"], x["fc"], x["fw"])
    mg = (x["gpo"], x["gouts"], gidx, x["gc"])
    proj = (x["h"], x["w"])
    berrut = (x["q"], x["bc"])
    flash = (x["aq"], x["ak"], x["av"])
    dattn = (x["dq"], x["dk"], x["dv"], x["pos"])
    return [
        ("parity_encode", ops.parity_encode_op, enc,
         ref.parity_encode_ref, enc, KERNEL_TOL_F32),
        ("parity_decode", lambda p, o, c: ops.parity_decode_op(p, o, 1, c),
         dec, ref.parity_decode_ref, (dec[0], dec[1], avail, 1 / x["c"][1]),
         KERNEL_TOL_F32),
        ("fused_encode_forward r=2", ops.fused_encode_forward_op, fused,
         ref.fused_encode_forward_ref, fused, KERNEL_TOL_BF16),
        (f"multigroup_decode G={G}", ops.multigroup_decode_op, mg,
         ref.multigroup_decode_ref, (mg[0], mg[1], gcmat), KERNEL_TOL_F32),
        ("learned_project r=2", ops.learned_project_op, proj,
         ref.learned_project_ref, proj, KERNEL_TOL_F32),
        ("berrut_encode r=2", ops.berrut_encode_op, berrut,
         ref.berrut_encode_ref, berrut, KERNEL_TOL_F32),
        ("flash_attention", ops.flash_attention_op, flash,
         ref.flash_attention_ref, flash, KERNEL_TOL_BF16),
        ("decode_attention", ops.decode_attention_op, dattn,
         ref.decode_attention_ref,
         dattn[:3] + (x["pos"][:, None, None],), KERNEL_TOL_BF16),
    ]


def phase_kernels(cfg, params, sz, seed, *, mosaic):
    """``mosaic``: every kernel must lower to a Mosaic ``tpu_custom_call``
    (the chip run); off the chip the interpreter runs them instead."""
    for name, op, args, oracle, oracle_args, tol in kernel_cases(cfg, sz,
                                                                 seed):
        compiled = jax.jit(op).lower(*args).compile()
        if mosaic and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"kernels: {name} did not compile to a "
                                 "Mosaic kernel (no tpu_custom_call)")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = oracle(*oracle_args)
        check("kernels", f"{name} {tuple(got.shape)} {got.dtype}",
              rel_err(got, want), tol)


def phase_pallas_lm(cfg, params, sz, seed, *, mosaic):
    """The lm path on the Pallas attention kernels against the jnp backend
    (part of the ``kernels`` phase)."""
    pcfg = cfg.replace(attn_backend="pallas")
    spec = GenerationSpec(cfg=cfg, params=params, k=2, r=1,
                          batching=BatchingPolicy(max_size=sz.slots),
                          max_seq_len=sz.max_seq_len, max_new_tokens=4)
    pspec = spec.replace(cfg=pcfg)
    if mosaic:
        cache = jax.eval_shape(lambda: T.init_cache(pcfg, sz.slots,
                                                    sz.max_seq_len))
        hlo = jax.jit(lambda p, c, pos, t: T.decode_step(
            pcfg, p, c, pos, token=t)).lower(
                params, cache, jnp.zeros((sz.slots,), jnp.int32),
                jnp.zeros((sz.slots, 1), jnp.int32)).as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError("kernels: the pallas decode step holds no "
                                 "Mosaic kernel")
    tokens = seeded_tokens(cfg, sz.prompt_lens[0] + 1, seed + 2)
    got = step_logits(pspec, params, tokens)
    want = step_logits(spec, params, tokens)
    check("kernels", "pallas vs jnp prefill logits", rel_err(got[0], want[0]),
          LOGITS_TOL)
    check("kernels", "pallas vs jnp decode logits", rel_err(got[1], want[1]),
          LOGITS_TOL)
    rng = np.random.default_rng(seed + 3)
    prompts = [rng.integers(0, cfg.vocab, sz.prompt_lens[0]).tolist()
               for _ in range(min(4, sz.requests))]
    with deploy_lm(pspec, engine="threads") as sess:
        futs = [sess.submit(p) for p in prompts]
        if not sess.wait_all(sz.timeout_s):
            raise RuntimeError("kernels: pallas lm requests unfinished")
    if any(len(f.result()) != 4 for f in futs):
        raise AssertionError("kernels: a pallas lm request lost tokens")
    log("kernels", f"pallas lm served {len(futs)} requests x 4 tokens")


# ------------------------------------------------------------- classify ---
def phase_classify(sz, seed):
    from repro.configs.resnet18_cifar import IMAGE_SHAPE, PAPER_MODELS
    _, stages, n_out = PAPER_MODELS["resnet18s"]
    params = jax.jit(lambda key: cnn.init_resnet(
        key, IMAGE_SHAPE, stages=stages, n_out=n_out))(
            jax.random.PRNGKey(seed))
    fwd = jax.jit(cnn.resnet_fwd)
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(sz.classify_queries, 1) + IMAGE_SHAPE).astype(
        np.float32)
    jax.block_until_ready(fwd(params, xs[0]))       # compile off the clock
    slow, delay_s = instance_id("main", 0), 2 * sz.straggle_ms / 1e3
    spec = DeploymentSpec(
        fwd=fwd, params=params, parity_params=params, scheme="sum",
        backend="pallas", k=2, m=4,
        delay_fn=lambda iid: delay_s if iid == slow else 0.0)
    with deploy(spec, engine="threads") as sess:
        futs = []
        for x in xs:
            futs.append(sess.submit(x))
            time.sleep(delay_s / 50)
        if not sess.wait_all(sz.timeout_s):
            raise RuntimeError("classify: queries unanswered")
        report = sess.stats()
    outs = [f.result() for f in futs]
    if any(o is None or np.shape(o) != (1, n_out) for o in outs):
        raise AssertionError("classify: a query got no prediction")
    by = report.completed_by
    log("classify", f"{len(futs)} queries, completed_by={by}, "
                    f"median={report.median_ms:.2f}ms "
                    f"p99.9={report.p999_ms:.2f}ms")
    if by.get("parity", 0) <= 0:
        raise AssertionError("classify: no query was completed by parity")


# ----------------------------------------------------------- lm_sharded ---
def phase_lm_sharded(cfg, params, sz, seed, n_chips):
    mesh = make_test_mesh((1, n_chips), ("data", "model"))
    log("lm_sharded", f"mesh={dict(mesh.shape)} over "
                      f"{mesh.devices.ravel().tolist()}")
    spec = GenerationSpec(cfg=cfg, params=params, max_seq_len=sz.max_seq_len)
    tokens = seeded_tokens(cfg, sz.prompt_lens[0] + 1, seed + 1)
    want = step_logits(spec, params, tokens)           # one device
    got = step_logits(spec.replace(mesh=mesh),
                      place_inference_params(params, mesh), tokens)
    check("lm_sharded", "sharded vs unsharded prefill logits",
          rel_err(got[0], want[0]), LOGITS_TOL)
    check("lm_sharded", "sharded vs unsharded first decode step logits",
          rel_err(got[1], want[1]), LOGITS_TOL)
    phase_lm(cfg, params, sz, seed, mesh=mesh, phase="lm_sharded")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel lm phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log("setup", f"compile cache: {enable_compile_cache()}")
    device = phase_device(args.chips)
    sz = Sizes()
    t = time.perf_counter()
    cfg, params = lm_model(sz, args.seed)
    log("setup", f"{cfg.name}: {T.param_count(params) / 1e9:.3f}B params "
                 f"({cfg.dtype}) initialised in {time.perf_counter() - t:.2f}s")
    if args.chips == 4:
        phase_lm_sharded(cfg, params, sz, args.seed, args.chips)
    else:
        phase_lm(cfg, params, sz, args.seed)
        phase_kernels(cfg, params, sz, args.seed, mosaic=True)
        phase_pallas_lm(cfg, params, sz, args.seed, mosaic=True)
        phase_classify(sz, args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
