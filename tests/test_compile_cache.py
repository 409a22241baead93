"""The persistent compilation cache helper: JAX_COMPILATION_CACHE_DIR
when it is set, otherwise the fixed, git-ignored <checkout>/.jax_cache."""

import os
import pathlib
import subprocess
import sys

import jax

from repro.compile_cache import enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_the_fixed_checkout_dir(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_entries_land_only_in_the_env_dir(tmp_path):
    cache = tmp_path / "cache"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7))"
            ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src"))
    before = (set(os.listdir(ROOT / ".jax_cache"))
              if (ROOT / ".jax_cache").exists() else set())
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    written = set(os.listdir(cache))
    assert written
    after = (set(os.listdir(ROOT / ".jax_cache"))
             if (ROOT / ".jax_cache").exists() else set())
    # other test workers may add their own entries to the checkout
    # cache meanwhile; this program's entries must not be among them
    assert not written & (after - before)
