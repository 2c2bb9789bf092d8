"""Persistent XLA compilation cache wiring (DESIGN.md §13).

A restarted serving process pays its biggest cold-start cost re-jitting
programs that an identical previous process already compiled.
:func:`enable_compile_cache` turns on JAX's persistent compilation cache
so the second process start performs ZERO new compilations — the CI
cold-start smoke asserts exactly that via :func:`cache_entries`.

Where the cache lives follows one rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
    leaves that setting alone and names no other directory;
  * otherwise an explicit ``path`` (tests that need their own directory),
    else :data:`DEFAULT_DIR`, one fixed directory inside the checkout
    (git-ignored).  The path is part of every entry's key, so it is never
    a temporary, per-process or time-stamped directory.

Two more rules make the zero-recompile guarantee hold:

  * call this BEFORE the first trace — each entry point (``serve.main``,
    ``dse_study.main``, ``benchmarks/run.py``, ``chip_smoke.py``) does it
    at the top of ``main()``, never at import;
  * use identical jax config across runs — config knobs are folded into
    the cache key, so a run that flips any compilation-affecting option
    misses every entry the previous run wrote.

The thresholds are forced to "cache everything" (min entry size -1, min
compile time 0) because serving decode/prefill programs on CPU smoke
shapes compile fast but numerous — exactly the programs a restart
re-pays.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.compile_cache — src/repro/launch/cache.py is three levels down
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".compile_cache")


def enable_compile_cache(path: str | None = None) -> str:
    """Enable jax's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (left as JAX configured it),
    else ``path``, else :data:`DEFAULT_DIR`."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = os.path.abspath(path or DEFAULT_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of committed compilation-cache entries under ``path``.
    Unchanged across a run == that run compiled nothing new."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
