"""CPU-sized copies of the benchmark's cells, for the tests.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp`` and
cuts every configuration and mix down to ``SIZES``, so that the harness's
own functions run each cell end to end on the CPU in seconds.  Nothing else
changes: the same driver, generator, fault plans, checks and readers run.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.harness import ROOT

# configuration name -> {section: {key: value}}
SIZES = {
    "olmo-1b": {
        "model": {"d_model": 64, "n_layers": 2, "n_heads": 4,
                  "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                  "vocab": 256, "dtype": "float32"},
        "deployment": {"slots": 2, "pool_positions": 64,
                       "straggle_ms": 30.0},
        "correct": {"sample_columns": 2, "reference_len": 48,
                    "max_token_gap": 1e-3},
    },
    "resnet18-cifar": {
        "model": {"stages": [8, 16, 16, 32]},
        # at these widths, on the CPU, the program read 1.6e-7 to 5.3e-7 and
        # the three-pass control (``cpu_high_precision``) 6.2e-6 to 1.9e-5
        # (5 seeds of each cell)
        "correct": {"sample_members": 24, "sample_reconstructed": 8,
                    "reference_block": 16, "max_member_logit_err": 2e-6,
                    "max_recon_logit_err": 2e-6},
    },
}
# mix loop -> the parameters a CPU-sized mix of that loop takes
MIXES = {
    "closed_waves": {
        "wave_size": 4,
        "prompt_len": {"dist": "lognormal", "mean": 10.0, "sigma": 1.0,
                       "buckets": [8, 16]},
        "output_len": {"dist": "lognormal", "mean": 8.0, "sigma": 0.5,
                       "lo": 4, "hi": 16, "strata": 2}},
    "closed_queries": {"clients": 4, "inputs": {"distinct": 64}},
}
# every slowed job misses the CPU-sized deadline, so a run of a few seconds
# reconstructs whenever a member is slowed and its parity is not
FAULTS = {"delay_ms": [45.0, 60.0]}

# every cell the tests drive: a cell that BENCHMARK.json does not (yet)
# measure on the chip is added to the copy
CELLS = {
    "olmo1b-batch-straggle": ("olmo-1b", "batch-straggle"),
    "olmo1b-batch-calm": ("olmo-1b", "batch-calm"),
    "resnet18-query-straggle": ("resnet18-cifar", "query-straggle"),
    "resnet18-query-calm": ("resnet18-cifar", "query-calm"),
}
# mix loop -> metrics every cell of that loop reports
METRICS = {
    "closed_waves": {"end_to_end": ["tokens_per_s"],
                     "per_layer": ["recon_share.lm", "queue_wait_p90_ms.lm",
                                   "compiles_in_window.lm"]},
    "closed_queries": {"end_to_end": ["queries_per_s"],
                       "per_layer": ["query_p50_ms.q",
                                     "compiles_in_window.q"]},
}


def _add_cell(bench, name, config, mix, loop):
    """Add a cell and the metrics every cell of its loop reports to
    ``bench``."""
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": mix, "chips": 1,
                               "why": "rehearsal"})
    for section, names in METRICS[loop].items():
        for metric in names:
            entry = next(m for m in bench[section] if m["name"] == metric)
            entry.setdefault("workloads", []).append(name)


def make_root(tmp: Path) -> Path:
    tmp = Path(tmp)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    have = {w["name"] for w in bench["workloads"]}
    for name, (config, mix) in CELLS.items():
        if name not in have:
            loop = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                              .read_text())["loop"]
            _add_cell(bench, name, config, mix, loop)
    for c in bench["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        for section, changes in SIZES.get(c["name"], {}).items():
            cfg[section].update(changes)
        path.write_text(json.dumps(cfg, indent=1))
    for path in (tmp / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(MIXES[mix["loop"]])
        path.write_text(json.dumps(mix, indent=1))
    for path in (tmp / "bench" / "faults").glob("*.json"):
        plan = json.loads(path.read_text())
        if plan["kind"] == "tenant_windows":
            plan.update(FAULTS)
        path.write_text(json.dumps(plan, indent=1))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def three_pass(product):
    """``product(x, w, ...)`` of float32 operands as the TPU computes it at
    matmul precision "high": each operand split into a bfloat16 head and a
    bfloat16 tail, and head*head + head*tail + tail*head summed in float32
    (the tail*tail term and what lies below the tails are dropped)."""
    def three(x, w, *args):
        xh, wh = _bf16(x), _bf16(w)
        xl, wl = _bf16(x - xh), _bf16(w - wh)
        return (product(xh, wh, *args) + product(xh, wl, *args)
                + product(xl, wh, *args))
    return three


def cpu_high_precision(monkeypatch):
    """The CPU computes float32 products in full at any matmul precision.
    Where the precision is "high", make the program's convolutions three
    bfloat16 passes (``three_pass``), as a TPU computes them, so that a CPU
    run of a query cell's control takes the same three passes."""
    from repro.models import cnn
    real = cnn.conv2d
    three = three_pass(real)

    def conv2d(x, w, stride=1):
        if jax.config.jax_default_matmul_precision == "high":
            return three(x, w, stride)
        return real(x, w, stride)
    monkeypatch.setattr(cnn, "conv2d", conv2d)
