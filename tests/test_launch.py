"""`chip_smoke.py` refuses to pass anywhere but on a TPU with the repo."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu_checkout", "script_alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """On the CPU backend, and in a directory holding only the script,
    it exits non-zero and its last line reports `"ok": false`."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False


CACHE_PROBE = """
import os, sys
import jax
from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache
where = enable_compile_cache()
print(where)
print(jax.config.jax_compilation_cache_dir)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(tmp_path, env_dir):
    """`JAX_COMPILATION_CACHE_DIR` wins and compiled programs land there;
    without it the cache is the checkout's fixed `.jax_cache`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    where, configured = out.stdout.split()[-2:]
    want = tmp_path / "cache" if env_dir else ROOT / ".jax_cache"
    assert pathlib.Path(where) == want
    assert pathlib.Path(configured) == want
    if env_dir:
        assert any((tmp_path / "cache").iterdir())
