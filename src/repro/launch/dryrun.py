"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination and extract memory / cost / collective evidence.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k [--multi-pod] [--mesh test]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices so
# jax.make_mesh can build the production mesh. This MUST precede any other
# import — jax locks the device count on first init.  The placeholders are
# host devices: the dry-run is a CPU-only tool and never takes an attached
# accelerator, which another process may be using.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.distributed.logical import logical_rules, rules_for_mesh
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.launch import steps as ST
from repro.launch.roofline import analyze, active_param_count, model_flops
from repro.training.optim import adam_init


def _mesh_for(name):
    if name == "pod":
        return make_production_mesh(multi_pod=False)
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    if name == "pod_serve8":
        # serving mesh with tp aligned to GQA kv-head counts (§Perf):
        # same 256 chips, (data=32, model=8)
        return make_test_mesh((32, 8), ("data", "model"))
    if name == "test":
        return make_test_mesh((2, 2), ("data", "model"))
    raise ValueError(name)


def adapt_config(arch, shape_name):
    """Per-shape config adjustments, recorded in the output notes."""
    cfg = get_config(arch)
    notes = []
    if shape_name == "long_500k" and not cfg.subquadratic:
        # pure full-attention archs run long-context decode with the
        # sliding-window variant (DESIGN.md §4 'Skips')
        cfg = cfg.replace(sliding_window=8192)
        notes.append("sliding_window=8192 for long_500k")
    return cfg, notes


def run_pair(arch, shape_name, mesh_name="pod", verbose=True,
             step_override=None, microbatch=0):
    t0 = time.time()
    cfg, notes = adapt_config(arch, shape_name)
    sh = SHAPES[shape_name]
    mesh = _mesh_for(mesh_name)
    chips = int(mesh.devices.size)

    pshapes = ST.param_shapes(cfg)
    n_params = ST.n_params_of(pshapes)
    # inference layout: replicate weights over 'data' (no per-layer FSDP
    # gathers) whenever tp-sharded bf16 params fit comfortably in HBM
    tp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    fsdp_params = not (sh.kind != "train"
                       and n_params * 2 / tp_size < 8e9)
    if not fsdp_params:
        notes.append("inference layout: params replicated over data axis")
    rules = ShardingRules(mesh, fsdp_params=fsdp_params)

    psh = rules.params(pshapes)
    batch = ST.input_specs(cfg, shape_name)
    bsh = rules.batch_specs(batch)
    rep = rules.replicated()

    lrules, lsizes = rules_for_mesh(mesh)
    lrules["fsdp_params"] = fsdp_params
    with mesh, logical_rules(lrules, lsizes, mesh):
        if sh.kind == "train":
            opt_cfg = ST.pick_opt_config(cfg, n_params)
            oshapes = jax.eval_shape(lambda p: adam_init(p, opt_cfg),
                                     pshapes)
            osh = rules.opt_state(oshapes, psh)
            fn = step_override(cfg, opt_cfg) if step_override else \
                ST.make_train_step(cfg, opt_cfg,
                                   batch_axes=rules.batch_axes,
                                   microbatch=microbatch)
            jitted = jax.jit(fn, in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, rep),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, batch)
        elif sh.kind == "prefill":
            fn = step_override(cfg) if step_override else \
                ST.make_prefill_step(cfg)
            out_shapes = jax.eval_shape(fn, pshapes, batch)
            csh = rules.cache_specs(out_shapes[1])
            lsh = rules.logits_spec(sh.global_batch, cfg.vocab)
            jitted = jax.jit(fn, in_shardings=(psh, bsh),
                             out_shardings=(lsh, csh))
            lowered = jitted.lower(pshapes, batch)
        else:  # decode
            cshapes = ST.cache_shapes(cfg, shape_name)
            csh = rules.cache_specs(cshapes)
            lsh = rules.logits_spec(sh.global_batch, cfg.vocab)
            fn = step_override(cfg, sh.seq_len - 1) if step_override else \
                ST.make_decode_step(cfg, sh.seq_len - 1)
            jitted = jax.jit(fn, in_shardings=(psh, csh, bsh),
                             out_shardings=(lsh, csh), donate_argnums=(1,))
            lowered = jitted.lower(pshapes, cshapes, batch)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    roof = analyze(compiled, chips)
    # XLA cost_analysis counts while-loop bodies ONCE (verified: compute
    # term scaled 1/m under m-way microbatching). The layer-stack scan
    # dominates both flops and collective volume, so we also report terms
    # scaled by its trip count (x enc groups for enc-dec; x microbatch).
    # Inner scans (flash KV blocks, SSD chunks) are still counted once —
    # the corrected numbers are lower bounds. Peak-memory numbers from
    # memory_analysis are exact either way.
    scan_trips = cfg.n_groups
    if cfg.enc_dec:
        scan_trips += cfg.n_enc_layers // cfg.period
    scan_trips *= max(1, microbatch)
    n_active = active_param_count(cfg, n_params)
    n_tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    mf = model_flops(cfg, n_tokens, active_params=n_active)
    if sh.kind == "train":
        mf *= 3.0                      # fwd + bwd
    hlo_flops_total = roof.flops_per_device * chips * scan_trips

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": sh.kind, "n_params": n_params, "n_active_params": n_active,
        "notes": notes,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_live_bytes": mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes,
        },
        "roofline": roof.as_dict(),
        "scan_trips": scan_trips,
        "roofline_scan_corrected": {
            "compute_s": roof.compute_s * scan_trips,
            "memory_s": roof.memory_s * scan_trips,
            "collective_s": roof.collective_s * scan_trips,
        },
        "microbatch": microbatch,
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_flops_total
                               if hlo_flops_total else None),
        "compile_s": time.time() - t0,
    }
    if verbose:
        r = roof
        print(f"{arch:24s} {shape_name:12s} {mesh_name:8s} "
              f"compute={r.compute_s*1e3:9.3f}ms memory={r.memory_s*1e3:9.3f}ms "
              f"coll={r.collective_s*1e3:9.3f}ms dom={r.dominant:10s} "
              f"temp/chip={mem.temp_size_in_bytes/2**30:6.2f}GiB "
              f"({result['compile_s']:.0f}s)", flush=True)
    return result


def save(result, out_dir="experiments/dryrun"):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "{arch}__{shape}__{mesh}.json".format(
        **result))
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "pod_serve8", "test"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=0)
    args = ap.parse_args()
    mesh_name = "multipod" if args.multi_pod else args.mesh

    pairs = ([(a, s) for a in ARCH_IDS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    failures = []
    for arch, shape in pairs:
        try:
            res = run_pair(arch, shape, mesh_name,
                           microbatch=args.microbatch)
            save(res, args.out)
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            print(f"FAIL {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("dry-run OK")


if __name__ == "__main__":
    main()
