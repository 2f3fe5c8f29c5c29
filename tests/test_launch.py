"""Entry-point plumbing: the compile-cache helper and the chip smoke's
refusal to run anywhere but a TPU."""

import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX already reads it, the helper sets nothing
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # unset: a fixed directory inside the checkout
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "tpu" in r.stderr
