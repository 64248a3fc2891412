"""Entry-point contracts: `chip_smoke.py` refuses to run off a TPU (or
without the repository beside it), and the entry points' persistent
compilation cache lands in exactly one directory."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_off_tpu():
    """On the CPU the smoke exits non-zero before any phase and prints
    no result line."""
    r = _run_smoke(ROOT, ROOT / "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)


def test_compile_cache_env_wins(monkeypatch, cache_config, tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper then sets
    nothing, so that directory stays the only one."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
