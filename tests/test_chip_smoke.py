"""CPU rehearsal of chip_smoke.py: every phase's control flow at reduced
size, with the Pallas kernels interpreted.  The script's own ``main`` still
refuses any platform but a TPU; the phases are what it runs there."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(smoke):
    return smoke.lm_model(smoke.REHEARSAL, seed=0)


@pytest.mark.parametrize("phase", ["lm", "kernels", "pallas_lm",
                                   "classify", "lm_sharded"])
def test_phase_rehearses_on_cpu(smoke, model, phase, capsys):
    cfg, params = model
    sz = smoke.REHEARSAL
    if phase == "lm":
        smoke.phase_lm(cfg, params, sz, seed=0)
    elif phase == "kernels":
        smoke.phase_kernels(cfg, params, sz, seed=0, mosaic=False)
    elif phase == "pallas_lm":
        smoke.phase_pallas_lm(cfg, params, sz, seed=0, mosaic=False)
    elif phase == "classify":
        smoke.phase_classify(sz, seed=0)
    else:                               # one host device: a (1, 1) mesh
        smoke.phase_lm_sharded(cfg, params, sz, seed=0, n_chips=1)
    prefix = "kernels" if phase == "pallas_lm" else phase
    assert f"[{prefix}]" in capsys.readouterr().out


def test_main_refuses_cpu():
    """No accelerator: exit nonzero at the device phase, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "is not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout
