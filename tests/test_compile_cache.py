"""The persistent compilation cache is placed from outside or at one fixed
path (deeplearning4j_tpu/compile_cache.py): with
``JAX_COMPILATION_CACHE_DIR`` set the code sets no directory; unset, every
process of a checkout resolves the same ``<checkout>/.jax_cache``."""

import os
import subprocess
import sys

import jax

from deeplearning4j_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(compile_cache, "_CONFIGURED", None)
    return calls


def test_env_dir_is_left_to_jax(tmp_path, monkeypatch):
    d = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.ensure_compile_cache() == d
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]
    # every compile persists, wherever the cache lives
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in calls
    assert compile_cache.compile_cache_stats()["dir"] == d


def test_unset_env_uses_the_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recorded_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.ensure_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in calls
    # idempotent: the second call touches no config
    del calls[:]
    assert compile_cache.ensure_compile_cache() == want
    assert calls == []


def test_path_is_identical_across_processes(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = ("from deeplearning4j_tpu.compile_cache import compile_cache_dir;"
            "print(compile_cache_dir())")
    seen = {subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, check=True,
                           timeout=60).stdout.strip()
            for cwd in (REPO, str(tmp_path))}
    assert seen == {compile_cache.compile_cache_dir()}
    assert seen == {os.path.join(REPO, ".jax_cache")}
