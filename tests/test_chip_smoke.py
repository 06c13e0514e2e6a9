"""CPU checks of the chip entry point and of the compile-cache placement.

``chip_smoke.py`` must refuse to run without a TPU (it never carries on
on the CPU), also when it is copied away from the repository; the
compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise
settles on one fixed, git-ignored directory inside the checkout."""
import os
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_smoke(cwd, script, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu(tmp_path):
    out = _run_smoke(ROOT, os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), lone)
    out = _run_smoke(str(lone), str(lone / "chip_smoke.py"), tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.use_compile_cache()
        assert compile_cache.use_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
