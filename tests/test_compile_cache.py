"""Where the entry points put JAX's persistent compilation cache: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, the fixed
``<repo>/.jax_cache`` otherwise.  Each case runs in a fresh interpreter so
that no cache setting leaks into the test process."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir, compile_one=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = PROBE
    if compile_one:
        code += ("jax.config.update("
                 "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                 "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    cache = tmp_path / "cache"
    returned, configured = _probe(cache, compile_one=True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir())


def test_unset_env_uses_the_fixed_repo_dir():
    returned, configured = _probe(None)
    assert returned == configured == str(ROOT / ".jax_cache")
