"""Shared fixtures: engine-cache hygiene + the persistent compile cache.

The cross-call engine cache (``repro.sim.engine``) is process-global, so a
test asserting on ``engine_cache_stats()`` counters (or on which engine a
call returns) would otherwise depend on which tests ran before it. Every
test starts from an empty cache with zeroed counters; caching behavior is
still fully exercised *within* each test (that is what the cache tests do).

Persistent compiles: when ``JAX_COMPILATION_CACHE_DIR=<dir>`` is exported,
every XLA compile in the test session is persisted there / reloaded from
there (``repro.sim.compile_cache``) — CI runs the compile-heavy suites
against an ``actions/cache``'d directory. Unset, the session caches nothing
on disk.
"""
from __future__ import annotations

import pytest

from repro.obs import close_sink, reset_metrics
from repro.sim import (
    enable_compile_cache,
    engine_cache_stats,
    persistent_cache_counters,
    reset_engine_cache,
)

_CACHE_DIR = enable_compile_cache()  # no-op (None) unless the env var is set


@pytest.fixture(scope="session", autouse=True)
def _engine_cache_clean_at_session_start():
    """Importing test modules (or plugins) must not populate the cache —
    a dirty cache at collection time would mean import-time engine builds."""
    stats = engine_cache_stats()
    assert stats == {"hits": 0, "misses": 0, "size": 0}, (
        f"engine cache dirty at session start: {stats}"
    )
    yield


@pytest.fixture(autouse=True)
def _fresh_engine_cache():
    """Order-independence: every test sees an empty engine cache and zeroed
    obs span/engine/lattice counters.

    PREFIX resets only: the ``compile_cache.`` registry namespace is
    process-lifetime — the session-end report below reads it across the
    whole run, so no per-test reset (or unscoped ``reset_metrics()``) may
    touch it.
    """
    reset_engine_cache()  # clears engines + the engine_cache. namespace
    for prefix in ("span.", "engine.", "lattice.", "multihost."):
        reset_metrics(prefix)
    yield
    close_sink()  # drop per-dir handles so tmp sink dirs can be removed


@pytest.fixture(scope="session", autouse=True)
def _persistent_cache_report():
    """Print the session's persistent-cache hit/miss counts when a cache
    directory is in use."""
    yield
    if _CACHE_DIR:
        counters = persistent_cache_counters()
        print(
            f"\npersistent compile cache {_CACHE_DIR}: "
            f"{counters['hits']} hit(s), {counters['misses']} miss(es)"
        )
