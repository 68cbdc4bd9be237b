"""util/compile_cache.py — the persistent compile cache is placed from
outside (JAX_COMPILATION_CACHE_DIR) or at one fixed path in the checkout."""

import os

import jax

from deeplearning4j_tpu.util import compile_cache


def test_env_placement_wins_and_no_code_sets_another_path(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_unset_env_uses_the_fixed_path_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    path = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    # fixed: the same on every call, nothing from tempfile/pid/time in it
    assert compile_cache.enable_compile_cache() == path


def test_cache_directory_is_git_ignored():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
