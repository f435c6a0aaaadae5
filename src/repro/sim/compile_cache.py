"""Durable XLA compiles: JAX's persistent compilation cache.

The lattice's dominant cold-call cost is the XLA compile (BENCH_sim.json's
``compile_seconds``), and it is identical across processes for identical
programs — so paying it once per *machine* instead of once per process is
pure win. The directory comes from JAX's own variable:

    JAX_COMPILATION_CACHE_DIR=~/.cache/repro-xla python -m benchmarks.run

When it is set, JAX reads it itself and this module sets no other
directory. When it is not, the entry scripts (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/*``) pass :data:`CHECKOUT_CACHE_DIR`, a
fixed path inside the checkout — never a temporary, per-process or timed
name, since a directory that moves never hits. Library code, the test
session (``tests/conftest.py``) and the ``repro.launch.distributed``
workers (which inherit the variable) call :func:`enable_compile_cache`
with no default, so they cache only where the variable says.

Hit accounting: :func:`enable_compile_cache` registers a
``jax.monitoring`` listener counting the ``/jax/compilation_cache/*``
events, exposed by :func:`persistent_cache_counters` — within one process a
program compiled earlier in the SAME process hits jax's in-memory caches
first, so persistent hits are expected on *fresh* processes.
"""
from __future__ import annotations

import os
from typing import Any

import jax
from jax import monitoring

from repro.obs.registry import counter_add, metric_value

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — listed in .gitignore
CHECKOUT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)

# hit/miss counts live in the obs registry under ``compile_cache.`` —
# PROCESS-LIFETIME counters, so nothing may reset that namespace mid-process
_LISTENER_INSTALLED = False


def _count_cache_events(event: str, **kwargs: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        counter_add("compile_cache.hits")
    elif event == "/jax/compilation_cache/cache_misses":
        counter_add("compile_cache.misses")


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    monitoring.register_event_listener(_count_cache_events)
    _LISTENER_INSTALLED = True


def enable_compile_cache(default_dir: str | None = None) -> str | None:
    """Enable the persistent compilation cache; returns the cache dir or None.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise ``default_dir``
    is used; with neither this is a no-op (None). The directory is created,
    every-compile persistence is forced (min-entry-size/min-compile-time
    floors dropped — the lattice's many small sub-programs should all hit on
    the next process), and the hit/miss listener is installed.
    """
    path = os.environ.get(ENV_CACHE_DIR) or default_dir
    if not path:
        return None
    path = os.path.abspath(os.path.expanduser(path))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _install_listener()
    return path


def persistent_cache_counters() -> dict:
    """This process's persistent-cache hit/miss counts (since enable).

    Thin shim over the obs registry (``compile_cache.hits`` / ``.misses``).
    """
    return {
        "hits": int(metric_value("compile_cache.hits")),
        "misses": int(metric_value("compile_cache.misses")),
    }


def cache_dir_entries(path: str | None = None) -> int:
    """Number of cache payload files in ``path`` (default: the directory
    ``$JAX_COMPILATION_CACHE_DIR`` names) — 0 for unset/missing. jax writes
    one ``*-cache`` payload (plus an ``-atime`` sidecar under LRU budgeting)
    per compiled program."""
    path = path or os.environ.get(ENV_CACHE_DIR) or None
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
