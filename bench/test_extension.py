"""A configuration, a traffic mix, a fault plan and metrics are added by
new files and new entries alone, for either loop (LM waves or one-shot
queries); and the command refuses what is not a chip run."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.harness import ROOT

# a new configuration: a small dense decoder of its own sizes, with its
# module beside it (here the OLMo module's text, as a new model's author
# would start from it)
CONFIG = dict(json.loads((ROOT / "bench" / "configs" / "olmo-1b.json")
                         .read_text()), name="dummy-lm",
              source="https://example.org/dummy")
CONFIG["model"] = dict(CONFIG["model"], d_model=32, n_layers=1, n_heads=2,
                       n_kv_heads=2, head_dim=16, d_ff=64, vocab=128,
                       dtype="float32")
CONFIG["deployment"] = dict(CONFIG["deployment"], slots=2,
                            pool_positions=48, straggle_ms=30.0)
CONFIG["correct"] = dict(CONFIG["correct"], sample_columns=2,
                         reference_len=40, max_token_gap=1e-3)
MIX = {"loop": "closed_waves", "wave_size": 4,
       "prompt_len": {"dist": "uniform", "lo": 4, "hi": 12,
                      "buckets": [6, 12]},
       "output_len": {"dist": "uniform", "lo": 3, "hi": 9, "strata": 3},
       "faults": "dummy-fault"}
FAULT = {"kind": "tenant_windows", "n_tenants": 2,
         "duration_ms": [50, 100], "gap_ms": [50, 100],
         "delay_ms": [45, 60], "first_ms": [0, 10]}
E2E = ('def read(run):\n'
       '    return min(b - a for r in run.requests\n'
       '               for a, b in zip(r.times[1:], r.times[2:])) * 1e3\n')
PER_LAYER = ('def read(run):\n'
             '    return sum(len(r.tokens) for r in run.requests) / '
             'max(r.wave for r in run.requests)\n')

# a new query configuration: a small CNN of its own sizes, its module
# beside it (the ResNet module's text), with a mix and metrics of its own
QCONFIG = dict(json.loads((ROOT / "bench" / "configs" / "resnet18-cifar.json")
                          .read_text()), name="dummy-cnn",
               source="https://example.org/dummy-cnn")
QCONFIG["model"] = dict(QCONFIG["model"], image_shape=[8, 8, 3],
                        blocks_per_stage=1, stages=[4, 8], n_classes=5)
QCONFIG["correct"] = dict(QCONFIG["correct"], sample_members=8,
                          sample_reconstructed=4, reference_block=8,
                          max_member_logit_err=2e-6,
                          max_recon_logit_err=2e-6)
QMIX = {"loop": "closed_queries", "clients": 3, "batch": 1,
        "inputs": {"distinct": 16}, "faults": "dummy-fault"}
# the same at k=3: a code whose reconstructions the query check cannot follow
QCONFIG_K3 = dict(QCONFIG, name="dummy-cnn-k3",
                  deployment=dict(QCONFIG["deployment"], k=3, m=6))
Q_E2E = ('def read(run):\n'
         '    return max(r.t_done - r.t_submit for r in run.requests) * 1e3\n')
Q_PER_LAYER = ('def read(run):\n'
               '    return sum(r.completed_by == "parity" '
               'for r in run.requests)\n')


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("ext")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = root / "bench"
    (b / "configs" / "dummy-lm.json").write_text(json.dumps(CONFIG))
    shutil.copy(b / "configs" / "olmo-1b.py", b / "configs" / "dummy-lm.py")
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(MIX))
    (b / "faults" / "dummy-fault.json").write_text(json.dumps(FAULT))
    (b / "metrics" / "fastest_gap_ms.py").write_text(E2E)
    (b / "metrics" / "tokens_per_wave.dummy.py").write_text(PER_LAYER)
    (b / "configs" / "dummy-cnn.json").write_text(json.dumps(QCONFIG))
    shutil.copy(b / "configs" / "resnet18-cifar.py",
                b / "configs" / "dummy-cnn.py")
    (b / "traffic" / "dummy-qmix.json").write_text(json.dumps(QMIX))
    (b / "configs" / "dummy-cnn-k3.json").write_text(json.dumps(QCONFIG_K3))
    shutil.copy(b / "configs" / "resnet18-cifar.py",
                b / "configs" / "dummy-cnn-k3.py")
    (b / "metrics" / "slowest_query_ms.py").write_text(Q_E2E)
    (b / "metrics" / "parity_answers.dummy.py").write_text(Q_PER_LAYER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-lm",
                             "source": CONFIG["source"],
                             "file": "bench/configs/dummy-lm.json",
                             "reduced": [], "why": "extension test"})
    bench["configs"].append({"name": "dummy-cnn",
                             "source": QCONFIG["source"],
                             "file": "bench/configs/dummy-cnn.json",
                             "reduced": [], "why": "extension test"})
    bench["configs"].append({"name": "dummy-cnn-k3",
                             "source": QCONFIG["source"],
                             "file": "bench/configs/dummy-cnn-k3.json",
                             "reduced": [], "why": "extension test"})
    bench["workloads"].append({"name": "dummy-qcell-k3",
                               "config": "dummy-cnn-k3",
                               "traffic": "dummy-qmix", "chips": 1,
                               "why": "extension test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-lm",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "extension test"})
    bench["workloads"].append({"name": "dummy-qcell", "config": "dummy-cnn",
                               "traffic": "dummy-qmix", "chips": 1,
                               "why": "extension test"})
    bench["end_to_end"].append({"name": "slowest_query_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["dummy-qcell"]})
    bench["per_layer"].append({"name": "parity_answers.dummy",
                               "unit": "count", "better": "higher",
                               "source": "program_counter",
                               "layer": "frontend",
                               "moves": "slowest_query_ms",
                               "workloads": ["dummy-qcell"]})
    bench["end_to_end"].append({"name": "fastest_gap_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    bench["per_layer"].append({"name": "tokens_per_wave.dummy",
                               "unit": "tokens", "better": "higher",
                               "source": "host_clock",
                               "layer": "scheduler", "moves": "fastest_gap_ms",
                               "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace,want", [
    (False, {"fastest_gap_ms", "setup_s"}),
    (True, {"tokens_per_wave.dummy"})])
def test_dummy_cell_runs_from_new_files(root, trace, want):
    line, checked, run = harness.run_cell(
        root, "dummy-cell", 3, 2.0, trace, devices=jax.devices(),
        trace_dir=root / "trace")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == want
    assert run.notes["fault_windows"] > 0
    assert {len(r.prompt) for r in run.requests} == {6, 12}
    assert checked["tokens_compared"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("trace,want", [
    (False, {"slowest_query_ms", "setup_s"}),
    (True, {"parity_answers.dummy"})])
def test_dummy_query_cell_runs_from_new_files(root, trace, want):
    line, checked, run = harness.run_cell(
        root, "dummy-qcell", 5, 2.0, trace, devices=jax.devices(),
        trace_dir=root / "qtrace")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == want
    assert run.notes["fault_windows"] > 0
    assert {r.output.shape for r in run.requests} == {(1, 5)}
    assert checked["reconstructed_compared"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0


def test_query_code_the_check_cannot_follow_is_refused(root):
    """A query configuration at k=3 is refused before anything is served:
    the check looks for one group partner per reconstructed prediction, so
    it follows the sum code at k=2, r=1 alone, and a k>2 configuration
    waits for a benchmark change that extends it."""
    with pytest.raises(ValueError, match="k=2, r=1"):
        harness.run_cell(root, "dummy-qcell-k3", 5, 1.0, False,
                         devices=jax.devices())


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_benchmark(ROOT)["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "is not 'tpu'" in out.stderr


def test_command_needs_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
