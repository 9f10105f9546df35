"""Persistent XLA compile cache: cross-process hits, opt-out, placement.

The cache is enabled on ``import repro.core`` (launch/compile_cache.py).
Cross-process behavior can only be observed from fresh interpreters, so
the hit test runs the same tiny solve in two subprocesses against a
private cache dir (JAX's own ``JAX_COMPILATION_CACHE_DIR``): the first
populates it, the second must add nothing.
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SOLVE_SNIPPET = """
from repro.core import solve, make_problem, mixing
from repro.data.synthetic import make_regression
data = make_regression(3, 6, 4, k=2, seed=0)
p = make_problem("ridge", data, mixing.ring_graph(3), lam=1e-2)
r = solve(p, "dsba", steps=4, record_every=2, alpha=0.1)
assert r.z.shape == (3, 4)
"""


def _run_child(cache_env):
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    env.pop("REPRO_NO_COMPILE_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(cache_env)
    proc = subprocess.run(
        [sys.executable, "-c", SOLVE_SNIPPET],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def _entries(cache_dir: Path) -> set[str]:
    if not cache_dir.exists():
        return set()
    return {p.name for p in cache_dir.rglob("*") if p.is_file()}


def test_second_process_hits_the_cache(tmp_path):
    cache = tmp_path / "xla_cache"
    env = {"JAX_COMPILATION_CACHE_DIR": str(cache)}
    _run_child(env)
    first = _entries(cache)
    assert first, "first process should populate the compile cache"
    _run_child(env)
    second = _entries(cache)
    # everything the second process compiled was served from disk
    assert second == first


def test_opt_out_env_disables_the_cache(tmp_path):
    cache = tmp_path / "xla_cache"
    _run_child({
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "REPRO_NO_COMPILE_CACHE": "1",
    })
    assert not _entries(cache)


def test_default_dir_is_repo_local_and_ignored():
    from repro.launch.compile_cache import default_cache_dir

    d = default_cache_dir()
    assert d == REPO / ".jax_compile_cache"
    assert ".jax_compile_cache" in (REPO / ".gitignore").read_text()


def test_standard_env_var_wins_and_code_sets_no_directory(
    tmp_path, monkeypatch
):
    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache, "_ENABLED", None)
    monkeypatch.setattr(
        jax.config, "update", lambda name, val: updates.append((name, val))
    )
    monkeypatch.delenv("REPRO_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_persistent_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in {name for name, _ in updates}

    # without the variable, the fixed checkout-local directory is set
    updates.clear()
    monkeypatch.setattr(compile_cache, "_ENABLED", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(REPO / ".jax_compile_cache")
    assert compile_cache.enable_persistent_cache() == want
    assert ("jax_compilation_cache_dir", want) in updates
