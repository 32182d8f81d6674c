"""The chip smoke refuses to run without a TPU, and the compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says or to one fixed path."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache
from repro.launch.compile_cache import compile_cache_dir, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert run.returncode != 0
    assert "needs a TPU" in run.stderr
    for line in run.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result without a TPU: {line}")


def test_cache_dir_from_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_enable_sets_a_directory_only_without_environment(monkeypatch,
                                                          tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() == str(compile_cache.REPO_CACHE)
    assert calls == [("jax_compilation_cache_dir",
                      str(compile_cache.REPO_CACHE))]
