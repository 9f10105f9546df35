"""Persistent XLA compilation cache: sweeps pay for each compile once, ever.

The runner caches in ``core.runner_cache`` already amortize compilation
*within* a process, but every fresh process (a new benchmark run, a pytest
tier, a CI job) still recompiles every chunked scan from scratch — and on
CPU those compiles dominate small-problem wall time. JAX ships a
content-addressed on-disk cache (``jax_compilation_cache_dir``) that
serializes compiled executables keyed by HLO + compile options + backend;
this module turns it on with repo-appropriate defaults.

``enable_persistent_cache()`` is called from ``repro.core.__init__`` so
every entrypoint (tests, benchmarks, notebooks) gets it without
ceremony. Policy:

* Where JAX's own ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
  it and this module sets no directory: the cache lands there and nowhere
  else.
* Otherwise the cache lives at the fixed ``<checkout>/.jax_compile_cache``
  (git-ignored). The path is part of what makes a later run hit, so it
  never moves; outside a source checkout no cache is enabled.
* ``REPRO_NO_COMPILE_CACHE`` (any non-empty value) disables the cache —
  the escape hatch for cold-start benchmarks and cache-behavior tests.
* Thresholds are zeroed (``min_compile_time_secs``/``min_entry_size``)
  because this repo's compiles are many-small: the default 1 s floor
  would exclude nearly everything we want cached.

Enabling is idempotent and silent; it never raises (an unwritable cache
dir degrades to a warning from XLA at worst, not a crash).
"""
from __future__ import annotations

import os
from pathlib import Path

_ENABLED: str | None = None  # cache dir once enabled, for introspection


def default_cache_dir() -> Path | None:
    """``<checkout>/.jax_compile_cache``, or None outside a source checkout.

    The checkout is the nearest parent of this file holding
    ``pyproject.toml``.
    """
    here = Path(__file__).resolve()
    for parent in here.parents:
        if (parent / "pyproject.toml").exists():
            return parent / ".jax_compile_cache"
    return None


def enable_persistent_cache() -> str | None:
    """Turn on JAX's on-disk compilation cache. Returns the dir, or None.

    Safe to call any number of times and before/after the first JAX
    computation (config updates apply to subsequent compiles). Honors
    ``JAX_COMPILATION_CACHE_DIR`` and ``REPRO_NO_COMPILE_CACHE``.
    """
    global _ENABLED
    if os.environ.get("REPRO_NO_COMPILE_CACHE"):
        return None
    if _ENABLED is not None:
        return _ENABLED
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    default = None if env_dir else default_cache_dir()
    if not env_dir and default is None:
        return None
    try:
        import jax

        if default is not None:
            jax.config.update("jax_compilation_cache_dir", str(default))
        # This repo compiles many small programs; the stock 1 s /
        # non-zero-size floors would skip nearly all of them.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    except Exception:  # pragma: no cover - never block import on cache setup
        return None
    _ENABLED = env_dir or str(default)
    return _ENABLED


def enabled_dir() -> str | None:
    """The active cache directory, or None if disabled/not yet enabled."""
    return _ENABLED
