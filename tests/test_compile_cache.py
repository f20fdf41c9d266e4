"""The one compile-cache rule (dynamo_tpu/runtime/compile_cache.py)."""

import os
import tempfile

import jax

from dynamo_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = "jax_compilation_cache_dir"


def _recorded_updates(monkeypatch):
    """Stub jax.config.update so the suite's own cache setting stays."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_env_var_set_means_code_sets_no_directory(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert KEY not in [key for key, _ in calls]


def test_unset_uses_the_fixed_path_inside_the_checkout(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = [compile_cache.enable_compile_cache(name)
           for name in ("serve", "serve", "tests")]
    assert got[0] == got[1] == os.path.join(REPO, ".jax_cache", "serve")
    assert got[2] == os.path.join(REPO, ".jax_cache", "tests")
    assert [v for k, v in calls if k == KEY] == got
    assert not any(p.startswith(tempfile.gettempdir()) for p in got)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_setter_in_the_tree():
    """grep -rn jax_compilation_cache_dir: one module names the option."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith(".py") or path == os.path.abspath(__file__):
                continue
            with open(path, errors="replace") as f:
                hits += [os.path.relpath(path, REPO)
                         for line in f if KEY in line]
    assert hits == [os.path.join("dynamo_tpu", "runtime", "compile_cache.py")]
