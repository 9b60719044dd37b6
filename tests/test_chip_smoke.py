"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size against
its own NumPy reference, and its refusal to run anywhere but on a TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_one_chip_phases_match_reference_on_cpu(capsys):
    """The Fig 1 join, both parameter bindings, whole-table scoring and all
    three forced strategies (Pallas in interpret mode) agree with the NumPy
    reference; the plans run in morsels and forced traversal on a prefix,
    as they do on the chip."""
    smoke = _load_smoke()
    smoke.one_chip(n_rows=4000, n_trees=4, max_depth=6, fit_rows=4000,
                   seed=0, chunk_rows=1024, traversal_rows=2500,
                   check_kernel=False)
    lines = capsys.readouterr().out.splitlines()
    labels = [line.split(":", 1)[0] for line in lines]
    for strategy in smoke.STRATEGIES:
        assert f"forced_{strategy}.check" in labels
    assert "param1.compiles" in labels
    assert not any(line.startswith("{") for line in lines)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_refuses_without_tpu_or_checkout(where, tmp_path):
    """Under JAX_PLATFORMS=cpu, and in a directory holding nothing of the
    repository but the script, it exits non-zero and prints no result."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        cwd = tmp_path
    proc = _run(cwd)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert "ok" not in json.loads(line)


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache lands there, and the
    helper sets no directory of its own."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n"
        "print(path)\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())
