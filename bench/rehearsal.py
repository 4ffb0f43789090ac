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
}
MIX = {"wave_size": 4,
       "prompt_len": {"dist": "lognormal", "mean": 10.0, "sigma": 1.0,
                      "buckets": [8, 16]},
       "output_len": {"dist": "lognormal", "mean": 8.0, "sigma": 0.5,
                      "lo": 4, "hi": 16, "strata": 2}}
# every slowed job misses the CPU-sized deadline, so a run of a few seconds
# reconstructs whenever a member is slowed and its parity is not
FAULTS = {"delay_ms": [45.0, 60.0]}

# every cell the tests drive: a cell that BENCHMARK.json does not (yet)
# measure on the chip is added to the copy
CELLS = {
    "olmo1b-batch-straggle": ("olmo-1b", "batch-straggle"),
    "olmo1b-batch-calm": ("olmo-1b", "batch-calm"),
}
METRICS = {"end_to_end": ["tokens_per_s"],
           "per_layer": ["recon_share.lm", "queue_wait_p90_ms.lm",
                         "compiles_in_window.lm"]}


def _add_cell(bench, name, config, mix):
    """Add a cell and the metrics every LM cell reports to ``bench``."""
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": mix, "chips": 1,
                               "why": "rehearsal"})
    for section, names in METRICS.items():
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
            _add_cell(bench, name, config, mix)
    for c in bench["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        for section, changes in SIZES.get(c["name"], {}).items():
            cfg[section].update(changes)
        path.write_text(json.dumps(cfg, indent=1))
    for path in (tmp / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(MIX)
        path.write_text(json.dumps(mix, indent=1))
    for path in (tmp / "bench" / "faults").glob("*.json"):
        plan = json.loads(path.read_text())
        if plan["kind"] == "tenant_windows":
            plan.update(FAULTS)
        path.write_text(json.dumps(plan, indent=1))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp
