"""Multi-host sharded lattice suite (ISSUE 4 tentpole pin).

Two layers:

  * in-process unit tests (fast, any environment) for the
    ``repro.sim.multihost`` plumbing — env contract, global mesh
    construction, shard assembly, record gathering, npz round-trip, worker
    env hygiene — all of which degrade to single-process behavior in the
    plain pytest process;
  * the ``@pytest.mark.distributed`` subprocess harness: drive
    ``repro.launch.distributed`` to run the parity workload as 2 coordinated
    ``jax.distributed`` processes × 4 fake CPU devices each, and assert the
    gathered records are DTYPE-EXACT against the in-process single-host
    (unsharded, 1-visible-device) run of the same ``LatticeSpec`` (sole
    carve-out: ``e_var``'s documented ≤1-ULP cross-topology codegen wobble —
    see ``_assert_records_equal``) — with zero engine retraces on the
    worker's repeat call (``n_lattice_traces`` guard, checked inside the
    worker where the multi-process trace lives).

The subprocess tests run in the dedicated ``distributed-cpu`` CI job
(``pytest -m distributed``); tier-1 CI deselects them to protect its budget.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch.distributed import (
    _RECORD_FIELDS,
    WorkerResult,
    load_records,
    parity_spec,
    run_parity_lattice,
    run_workers,
    save_records,
    worker_env,
)
from repro.sim import multihost
from repro.sim.lattice import make_cell_mesh
HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))


def _assert_records_equal(a, b, cross_topology: bool = False):
    """Dtype-exact structured equality.

    ``cross_topology=True`` (multi-process vs single-host) relaxes exactly
    ONE field: ``e_var`` — its ‖·‖² reduction over the full parameter dim
    picks up a deterministic ≤1-ULP difference from the process-spanning
    SPMD compilation (measured: 3/48 entries off by 2⁻²⁶ at ~0.1 scale,
    identical on every run; the single-process 8-device mesh is bit-exact,
    pinned by tests/test_lattice_sharded.py). Every other field — including
    the trajectory-critical loss/acc/grad_norm/e_com — must match bit for
    bit, and within one topology repeats are bit-identical (the worker's
    ``repeat_exact`` meta).
    """
    assert a.axes == b.axes
    np.testing.assert_array_equal(a.eval_rounds, b.eval_rounds)
    for f in _RECORD_FIELDS:
        fa, fb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert fa.shape == fb.shape, f
        assert fa.dtype == fb.dtype, f
        if cross_topology and f == "e_var":
            np.testing.assert_allclose(fa, fb, rtol=1e-6, err_msg=f)
        else:
            np.testing.assert_array_equal(fa, fb, err_msg=f)


# --------------------------------------------------------------------------
# in-process plumbing (single-process degradation paths)
# --------------------------------------------------------------------------


def test_distributed_env_contract(monkeypatch):
    monkeypatch.delenv(multihost.ENV_COORDINATOR, raising=False)
    assert multihost.distributed_env() is None
    monkeypatch.setenv(multihost.ENV_COORDINATOR, "127.0.0.1:1234")
    monkeypatch.setenv(multihost.ENV_NUM_PROCESSES, "2")
    monkeypatch.setenv(multihost.ENV_PROCESS_ID, "1")
    cfg = multihost.distributed_env()
    assert cfg == multihost.DistributedConfig("127.0.0.1:1234", 2, 1)
    # a PARTIAL contract is an operator error, not a silent single-process
    # fallback and not a bare KeyError from inside worker startup
    monkeypatch.delenv(multihost.ENV_NUM_PROCESSES)
    with pytest.raises(ValueError, match="REPRO_DIST_NUM_PROCESSES"):
        multihost.distributed_env()


def test_initialize_noop_without_topology(monkeypatch):
    """No env contract / single-process config → no jax.distributed init."""
    monkeypatch.delenv(multihost.ENV_COORDINATOR, raising=False)
    assert multihost.initialize_distributed() is False
    single = multihost.DistributedConfig("127.0.0.1:1", 1, 0)
    assert multihost.initialize_distributed(single) is False


def test_global_mesh_single_process_equals_local_mesh():
    """With one process the global device list IS the local one, so the two
    mesh constructors agree (and share an engine-cache identity)."""
    from repro.sim.engine import _mesh_key

    g = multihost.make_global_cell_mesh(1)
    l = make_cell_mesh(1)
    assert _mesh_key(g) == _mesh_key(l)
    assert not multihost.mesh_spans_processes(g)
    assert multihost.mesh_process_span(g) == (jax.process_index(),)


def test_global_mesh_validates_device_count():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        multihost.make_global_cell_mesh(n + 1)
    with pytest.raises(ValueError, match="devices"):
        multihost.make_global_cell_mesh(0)


def test_shard_to_global_and_gather_roundtrip():
    """Single-process degradation: assembly is a sliced device_put and the
    gather is a plain device_get — values and dtype survive the round trip."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = multihost.make_global_cell_mesh(1)
    sharding = NamedSharding(mesh, PartitionSpec("cells"))
    host = np.arange(6, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32)
    garr = multihost.shard_to_global(host, sharding)
    assert garr.shape == host.shape and garr.is_fully_addressable
    back = multihost.gather_records({"x": garr}, mesh)["x"]
    assert back.dtype == host.dtype
    np.testing.assert_array_equal(np.asarray(back), host)


def test_records_npz_roundtrip(tmp_path):
    recs, meta = run_parity_lattice(mesh=None, n_rounds=2)
    path = str(tmp_path / "recs.npz")
    save_records(path, recs, {"k": 1, **meta})
    loaded, got_meta = load_records(path)
    assert got_meta["k"] == 1 and got_meta["retrace_delta"] == 0
    _assert_records_equal(recs, loaded)


def test_records_npz_contract_drops_optional_subtrees(tmp_path):
    """save_records covers the FLAT array fields only: a records object
    carrying the optional ``eval``/``diag`` pytree subtrees must still save
    readable under np.load's ``allow_pickle=False`` default (a ``None``
    subtree would pickle as an object array; an ``EvalRecord`` would
    collapse into a bare ndarray) and load back with both subtrees ``None``
    — they travel via the in-process/obs paths, never the parity npz."""
    from repro.sim.tasks import EvalRecord

    recs, meta = run_parity_lattice(mesh=None, n_rounds=2)
    curve = np.zeros_like(np.asarray(recs.acc))
    carrying = recs._replace(
        eval=EvalRecord(loss=curve, acc=curve, n_correct=curve)
    )
    path = str(tmp_path / "recs_eval.npz")
    save_records(path, carrying, meta)
    loaded, _ = load_records(path)
    assert loaded.eval is None and loaded.diag is None
    _assert_records_equal(recs, loaded)


def test_worker_env_contract_and_device_pool():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --xla_foo=1",
            "PYTHONPATH": "/elsewhere"}
    env = worker_env("127.0.0.1:9", 2, 1, 4, base_env=base)
    assert env[multihost.ENV_COORDINATOR] == "127.0.0.1:9"
    assert env[multihost.ENV_NUM_PROCESSES] == "2"
    assert env[multihost.ENV_PROCESS_ID] == "1"
    # inherited device-count flag is REPLACED, other XLA flags survive
    assert env["XLA_FLAGS"].count("--xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--xla_foo=1" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    parts = env["PYTHONPATH"].split(os.pathsep)
    assert SRC in parts and "/elsewhere" in parts


def test_launcher_refuses_without_cpu_platform(monkeypatch):
    """The launcher is a CPU rehearsal tool: without JAX_PLATFORMS=cpu in
    the launching process it must refuse before spawning anything."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        worker_env("127.0.0.1:9", 2, 1, 4, base_env={})
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        run_workers([sys.executable, "-c", "pass"], n_procs=1,
                    devices_per_proc=1, timeout=60)


def test_run_workers_raises_on_any_failure():
    """The launcher must not report success over a half-failed topology."""
    argv = [
        sys.executable, "-c",
        "import os, sys; sys.exit(3 if os.environ['REPRO_DIST_PROCESS_ID'] == '1' else 0)",
    ]
    with pytest.raises(RuntimeError, match="worker 1"):
        run_workers(argv, n_procs=2, devices_per_proc=1, timeout=60)


def test_engine_cache_key_includes_process_topology():
    from repro.sim.engine import _process_topology_key

    assert _process_topology_key() == (jax.process_count(), jax.process_index())


# --------------------------------------------------------------------------
# the subprocess-driven 2-process × 4-fake-device parity harness
# --------------------------------------------------------------------------


@pytest.mark.distributed
def test_two_process_lattice_matches_single_host(tmp_path):
    """ISSUE 4 acceptance: drive the launcher CLI via subprocess — 2
    coordinated processes × 4 fake CPU devices run the parity LatticeSpec on
    a process-spanning global mesh — and compare the worker-0 records
    DTYPE-EXACTLY against the in-process single-host (unsharded) run of the
    same spec. Worker meta must prove the topology was real (2 processes, 8
    global / 4 local devices) and that the repeat call re-traced ZERO times.
    """
    out = str(tmp_path / "parity.npz")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    # the launcher's own worker deadline (450s) must trip BEFORE the outer
    # timeout (600s): the launcher then reaps its workers and reports their
    # output tails, instead of being killed around still-running grandchildren
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.distributed",
         "--procs", "2", "--devices-per-proc", "4",
         "--workload", "parity", "--out", out, "--timeout", "450"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout[-8000:])
        sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "distributed launcher failed"

    sharded, meta = load_records(out)
    assert meta["process_count"] == 2
    assert meta["n_global_devices"] == 8
    assert meta["n_local_devices"] == 4
    # zero retraces on the repeat sharded call, and bit-stable repeat records
    assert meta["retrace_delta"] == 0
    assert meta["repeat_exact"] is True
    # the policy-FUSED lattice: the whole 2-policy spec is one trace / one
    # compile inside the worker topology, and the per-policy fallback
    # reproduces it bit for bit across the process boundary
    assert meta["traces_first"] == 1
    assert meta["n_lattice_compiles"] == 1
    assert meta["fused_matches_fallback"] is True

    reference, ref_meta = run_parity_lattice(mesh=None)
    assert ref_meta["retrace_delta"] == 0
    _assert_records_equal(reference, sharded, cross_topology=True)

    # the parity grid must exercise dead-cell padding across the process
    # boundary: 6 real cells per policy on an 8-device global mesh
    spec = parity_spec()
    n_grid = len(spec.noise_powers) * len(spec.alphas) * len(spec.seeds)
    assert n_grid == 6 and meta["n_global_devices"] == 8


@pytest.mark.distributed
def test_launcher_generic_command_mode(tmp_path):
    """`-- command` mode: any script that initializes from the env contract
    runs under the launcher (here: examples/sim_lattice.py --distributed)."""
    example = os.path.abspath(os.path.join(HERE, "..", "examples", "sim_lattice.py"))
    results = run_workers(
        [sys.executable, example, "--distributed", "--rounds", "2"],
        n_procs=2, devices_per_proc=2, timeout=600,
    )
    assert all(isinstance(r, WorkerResult) and r.returncode == 0 for r in results)
    assert "cells sharded over 4 devices (2 hosts)" in results[0].output
