"""Entry points place JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` is set they leave it to JAX, otherwise they
use a fixed directory in the checkout that git ignores.  The cache is never
turned on here: ``jax.config.update`` is replaced by a recorder."""
from pathlib import Path

import jax

from repro import launch

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    launch.use_compile_cache()
    assert calls == []


def test_default_is_fixed_checkout_dir_ignored_by_git(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    launch.use_compile_cache()
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
