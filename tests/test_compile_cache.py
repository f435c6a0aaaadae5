"""Persistent compilation cache: the ``JAX_COMPILATION_CACHE_DIR`` contract.

In-process unit tests for the no-op/counter plumbing, plus a subprocess
sequence proving the directory rule and that compiles survive process
death: with the variable set, a cold process populates exactly that
directory (never the caller's default), a second fresh process compiling
the same program logs persistent-cache HITS, and with the variable unset
the caller's fixed default directory is used instead.
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.sim import compile_cache

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))

_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.sim.compile_cache import enable_compile_cache, persistent_cache_counters
print("DIR", enable_compile_cache(sys.argv[1]))
f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)
f(jnp.ones((32, 32))).block_until_ready()
print("HITS", persistent_cache_counters()["hits"])
"""


def test_enable_is_noop_without_contract(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    assert compile_cache.enable_compile_cache() is None
    assert compile_cache.cache_dir_entries() == 0


def test_cache_dir_entries_counts_payloads(tmp_path):
    (tmp_path / "a-cache").write_bytes(b"x")
    (tmp_path / "a-atime").write_bytes(b"x")  # LRU sidecar, not a payload
    (tmp_path / "b-cache").write_bytes(b"x")
    assert compile_cache.cache_dir_entries(str(tmp_path)) == 2
    assert compile_cache.cache_dir_entries(str(tmp_path / "missing")) == 0


def test_persistent_cache_hits_across_processes(tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is the only directory used: a cold
    process populates it and leaves the caller's default untouched, and a
    FRESH process compiling the same program is served from it (hits > 0)
    — in-memory jit caches cannot explain that, only the persistent layer
    can. Unset, the caller's default directory takes the compiles."""
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    base = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    base.pop(compile_cache.ENV_CACHE_DIR, None)

    def probe(env) -> tuple[str, int]:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE, str(default_dir)], env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        used = out.stdout.split("DIR")[1].split("\n")[0].strip()
        return used, int(out.stdout.split("HITS")[1].strip())

    with_env = dict(base, **{compile_cache.ENV_CACHE_DIR: str(env_dir)})
    cold_dir, cold_hits = probe(with_env)
    assert cold_dir == str(env_dir)
    assert compile_cache.cache_dir_entries(str(env_dir)) > 0
    assert not default_dir.exists()
    warm_dir, warm_hits = probe(with_env)
    assert warm_dir == str(env_dir)
    assert cold_hits == 0
    assert warm_hits > 0

    fallback_dir, _ = probe(base)
    assert fallback_dir == str(default_dir)
    assert compile_cache.cache_dir_entries(str(default_dir)) > 0
