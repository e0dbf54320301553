"""The chip entry points off the chip: where the compile cache goes, and
that ``chip_smoke.py`` refuses to report a result without a TPU."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # nothing set


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu — in the checkout, or copied alone to a
    directory without the program — the smoke exits non-zero and prints
    no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
