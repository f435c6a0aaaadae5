"""Local multi-process launcher for ``jax.distributed`` lattice runs.

Spawns N coordinated worker processes ON THIS MACHINE — a shared coordinator
address on localhost, a distinct process id per worker, and a per-worker
``XLA_FLAGS=--xla_force_host_platform_device_count=K`` fake CPU device pool —
so the multi-host lattice path (``repro.sim.multihost`` + ``run_lattice``
over a :func:`~repro.sim.multihost.make_global_cell_mesh`) runs end-to-end on
one CPU box. That makes multi-host a CI-testable code path instead of a
cluster-only one: tests/test_multihost_lattice.py drives this launcher via
``subprocess`` and asserts the 2-process × 4-fake-device lattice is
dtype-exact against the in-process single-host run of the same spec.

Worker contract (written into each child's environment — real multi-host
deployments export the same three variables per host instead):

    REPRO_DIST_COORDINATOR   host:port of process 0's coordination service
    REPRO_DIST_NUM_PROCESSES total process count
    REPRO_DIST_PROCESS_ID    this process's rank

Observability: the worker env copies the launcher's ``os.environ``, so a
``REPRO_OBS_DIR`` (``repro.obs``) set on the launcher is inherited by every
worker — each writes its own ``events-p<rank>of<count>-<pid>.jsonl`` into
the shared sink directory (the rank stamp comes from the same
``REPRO_DIST_*`` contract above), and ``python -m repro.obs.report <dir>``
summarizes the whole topology.

Usage (CPU CI / laptop):

    # built-in parity workload: 2 hosts × 4 fake devices, records → npz
    python -m repro.launch.distributed --procs 2 --devices-per-proc 4 \\
        --workload parity --out /tmp/records.npz

    # multihost throughput bench (benchmarks/run.py --hosts N calls this)
    python -m repro.launch.distributed --procs 2 --devices-per-proc 4 \\
        --workload bench --out /tmp/bench.json

    # any script that calls sim.initialize_distributed() itself
    python -m repro.launch.distributed --procs 2 --devices-per-proc 4 \\
        -- python examples/sim_lattice.py --distributed

This launcher is a CPU rehearsal tool: it refuses to spawn unless the
launching process itself runs with ``JAX_PLATFORMS=cpu``, and every worker
runs on the CPU. A chip belongs to one process at a time, so on a machine
with a chip these workers could only report CPU numbers as the machine's.
Real accelerator pods bring their own process launcher (SLURM/GKE) and
only need the env contract above.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import socket
import subprocess
import sys

import numpy as np

from repro.sim.engine import RoundRecord
from repro.sim.multihost import (
    ENV_COORDINATOR,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
)

# the canonical per-round ARRAY record fields (one source: the engine's
# RoundRecord, minus the optional pytree subtrees `diag`, `eval` and
# `health` — the npz parity serialization and cross-process comparisons
# cover the flat arrays only; obs diagnostics travel through the
# REPRO_OBS_DIR JSONL sink and eval curves through the in-process
# LatticeRecords/run_with_history paths instead. np.savez would pickle a
# None subtree as an object array (unreadable under allow_pickle=False)
# and collapse a NamedTuple leaf.)
_RECORD_FIELDS = tuple(
    f for f in RoundRecord._fields if f not in ("diag", "eval", "health")
)
_DEVICE_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=\S+\s*")


def find_free_port() -> int:
    """Bind-and-release a localhost TCP port for the coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class WorkerResult:
    process_id: int
    returncode: int
    output: str  # merged stdout+stderr


def require_cpu_platform() -> None:
    """Refuse to spawn workers unless this process runs with
    ``JAX_PLATFORMS=cpu`` (see the module docstring)."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "repro.launch.distributed spawns CPU workers only; run it with "
            "JAX_PLATFORMS=cpu (got JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        )


def worker_env(
    coordinator: str,
    num_processes: int,
    process_id: int,
    devices_per_proc: int,
    base_env: dict | None = None,
) -> dict:
    """Environment for one spawned worker: the ``REPRO_DIST_*`` contract plus
    a fresh fake-device pool (any inherited device-count flag is stripped —
    the child's pool must be exactly ``devices_per_proc``) and import roots
    matching the parent (``repro``'s src dir + the parent cwd, so workload
    code resolves ``benchmarks``/``examples`` the way the parent would).
    Raises unless this process runs with ``JAX_PLATFORMS=cpu``."""
    require_cpu_platform()
    env = dict(os.environ if base_env is None else base_env)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(process_id)
    inherited = _DEVICE_COUNT_FLAG.sub("", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}"
        + (f" {inherited}" if inherited else "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    import repro

    # namespace-package-safe (repro has no __init__.py, so __file__ is None)
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    roots = [src_root, os.getcwd()]
    if env.get("PYTHONPATH"):
        roots.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(roots)
    return env


def spawn_local(
    worker_argv: list[str],
    n_procs: int = 2,
    devices_per_proc: int = 4,
    timeout: float = 900.0,
    base_env: dict | None = None,
) -> list[WorkerResult]:
    """Run ``worker_argv`` as ``n_procs`` coordinated local processes.

    Every worker gets the same argv and the per-rank env contract; the call
    blocks until all exit. ``timeout`` is one ABSOLUTE deadline for the whole
    topology (workers run concurrently, so a wedged barrier costs one
    timeout, not one per rank); stragglers past it are killed with their
    output preserved. Results come back in rank order; nothing is raised on
    failure — see :func:`run_workers` for the raising wrapper.
    """
    import tempfile
    import time

    coordinator = f"127.0.0.1:{find_free_port()}"
    # build every env BEFORE the first spawn: a partial spawn would orphan
    # rank 0 blocking forever on the coordination barrier for ranks that
    # were never started
    envs = [
        worker_env(coordinator, n_procs, pid, devices_per_proc, base_env)
        for pid in range(n_procs)
    ]
    # each worker streams into its own temp file, never a pipe: sequential
    # pipe draining would wedge the topology as soon as one rank fills the
    # 64KB pipe buffer while an earlier rank still runs (ranks block on
    # each other through collectives, so output must never backpressure)
    outs = [tempfile.TemporaryFile(mode="w+") for _ in envs]
    procs = [
        subprocess.Popen(
            worker_argv, env=env, stdout=f, stderr=subprocess.STDOUT, text=True,
        )
        for env, f in zip(envs, outs)
    ]
    deadline = time.monotonic() + timeout
    deadline_killed = set()
    try:
        for rank, proc in enumerate(procs):
            try:
                proc.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                # a straggler can win the race and exit cleanly between the
                # timeout firing and the kill landing (kill on a reaped pid
                # is a no-op): only report a deadline kill when the recorded
                # returncode actually reflects one — never rewrite a real
                # exit status to -9
                if proc.returncode != 0:
                    deadline_killed.add(rank)
    finally:
        # ranks past the one that raised (or that an exception skipped) are
        # stragglers too: same kill, same bookkeeping
        for rank, proc in enumerate(procs):
            if proc.poll() is None:
                proc.kill()
                proc.wait()
                deadline_killed.add(rank)
    results = []
    for rank, (proc, f) in enumerate(zip(procs, outs)):
        f.seek(0)
        out = f.read()
        f.close()
        rc = proc.returncode if proc.returncode is not None else -9
        if rank in deadline_killed:
            out += f"\n[launcher] killed at the {timeout}s deadline (rc={rc})"
        results.append(WorkerResult(rank, rc, out))
    return results


def run_workers(
    worker_argv: list[str],
    n_procs: int = 2,
    devices_per_proc: int = 4,
    timeout: float = 900.0,
) -> list[WorkerResult]:
    """:func:`spawn_local` that raises ``RuntimeError`` (with output tails)
    when any worker exits nonzero — the launcher must never report success
    over a half-failed topology."""
    results = spawn_local(worker_argv, n_procs, devices_per_proc, timeout)
    failed = [r for r in results if r.returncode != 0]
    if failed:
        tails = "\n".join(
            f"--- worker {r.process_id} (rc={r.returncode}) ---\n{r.output[-4000:]}"
            for r in failed
        )
        raise RuntimeError(
            f"{len(failed)}/{len(results)} distributed workers failed:\n{tails}"
        )
    return results


# --------------------------------------------------------------------------
# supervised workers: per-rank restart with capped exponential backoff
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Per-rank supervision policy for :func:`supervise_workers`.

    ``max_restarts`` bounds restarts PER RANK (so one flapping rank cannot
    consume the whole budget of a healthy cohort); restart ``i`` waits
    ``min(backoff_base * 2**(i-1), backoff_cap)`` seconds first.
    ``liveness_timeout`` (seconds; None disables) declares a silent rank
    dead when its obs event files under the shared ``REPRO_OBS_DIR`` go
    that long without an mtime update — the chunked resilient workload
    heartbeats once per checkpoint chunk, so a wedged rank is killed and
    restarted instead of holding the topology to the absolute deadline."""

    max_restarts: int = 2
    backoff_base: float = 0.25
    backoff_cap: float = 8.0
    liveness_timeout: float | None = None
    poll_interval: float = 0.2


def supervise_workers(
    worker_argv: list[str],
    n_procs: int = 2,
    devices_per_proc: int = 1,
    timeout: float = 900.0,
    supervisor: SupervisorConfig | None = None,
    base_env: dict | None = None,
) -> list[WorkerResult]:
    """Run ``worker_argv`` as ``n_procs`` INDEPENDENT local workers, each
    under per-rank supervision: a rank that exits nonzero (crash, injected
    ``REPRO_FAULT_KILL``) or goes heartbeat-silent is restarted with capped
    exponential backoff, up to ``max_restarts`` times, and resumes from its
    own checkpoints. Replaces :func:`spawn_local`'s single absolute deadline
    for workloads that can re-enter (the deadline still exists as the outer
    backstop).

    UNLIKE :func:`spawn_local`, workers here must not rely on each other
    (no ``jax.distributed`` collectives): one rank is restarted alone while
    the others keep running, which would wedge a collective. The resilient
    lattice workload shards the fused cell grid into independent slices for
    exactly this reason.

    ``REPRO_FAULT_*`` is stripped from every RESTARTED rank's environment —
    injected faults are one-shot, so a supervised run recovers from the
    fault instead of re-firing it forever.

    Raises ``RuntimeError`` with per-rank output tails when any rank's
    restart budget is exhausted (or the absolute deadline fires); returns
    rank-ordered :class:`WorkerResult`\\ s (final attempt's rc/output,
    supervisor markers inline) on success.
    """
    import glob as _glob
    import tempfile
    import time

    from repro.obs.sink import emit, obs_dir
    from repro.sim.resilience import FAULT_ENV_VARS

    sup = supervisor or SupervisorConfig()
    coordinator = f"127.0.0.1:{find_free_port()}"
    sink = obs_dir() if base_env is None else (base_env.get("REPRO_OBS_DIR") or None)

    outs = [tempfile.TemporaryFile(mode="w+") for _ in range(n_procs)]
    procs: list[subprocess.Popen | None] = [None] * n_procs
    attempts = [0] * n_procs
    next_start = [0.0] * n_procs  # monotonic time before which a rank waits
    started_wall = [0.0] * n_procs
    done: list[WorkerResult | None] = [None] * n_procs
    deadline = time.monotonic() + timeout

    def note(rank: int, text: str) -> None:
        f = outs[rank]
        f.flush()
        f.seek(0, os.SEEK_END)  # the child shares the fd; never rewind it
        f.write(f"[supervisor] {text}\n")
        f.flush()

    def start(rank: int) -> None:
        env = worker_env(coordinator, n_procs, rank, devices_per_proc, base_env)
        if attempts[rank] > 0:
            for var in FAULT_ENV_VARS:  # injected faults are one-shot
                env.pop(var, None)
        note(rank, f"start rank {rank} attempt {attempts[rank]}")
        outs[rank].seek(0, os.SEEK_END)
        procs[rank] = subprocess.Popen(
            worker_argv, env=env,
            stdout=outs[rank], stderr=subprocess.STDOUT, text=True,
        )
        started_wall[rank] = time.time()

    def collect(rank: int) -> str:
        f = outs[rank]
        f.flush()
        f.seek(0)
        return f.read()

    def last_signal(rank: int) -> float:
        """Wall time of the rank's latest sign of life: its newest obs
        event-file mtime, floored at this attempt's start."""
        sig = started_wall[rank]
        if sink:
            pattern = os.path.join(
                sink, f"events-p{rank:03d}of{n_procs:03d}-*.jsonl"
            )
            for p in _glob.glob(pattern):
                try:
                    sig = max(sig, os.path.getmtime(p))
                except OSError:
                    pass
        return sig

    def on_crash(rank: int, rc: int, why: str) -> None:
        procs[rank] = None
        if attempts[rank] >= sup.max_restarts:
            note(rank, f"rank {rank} {why} (rc={rc}); restart budget "
                       f"({sup.max_restarts}) exhausted")
            done[rank] = WorkerResult(rank, rc if rc != 0 else 1, collect(rank))
            return
        attempts[rank] += 1
        delay = min(sup.backoff_base * 2 ** (attempts[rank] - 1), sup.backoff_cap)
        next_start[rank] = time.monotonic() + delay
        note(rank, f"rank {rank} {why} (rc={rc}); restart "
                   f"{attempts[rank]}/{sup.max_restarts} in {delay:.2f}s")
        emit(
            "supervisor", "supervisor.restart",
            rank=rank, rc=rc, attempt=attempts[rank], backoff=delay, why=why,
        )

    try:
        while any(d is None for d in done):
            now = time.monotonic()
            if now > deadline:
                for rank, proc in enumerate(procs):
                    if proc is not None and proc.poll() is None:
                        proc.kill()
                        proc.wait()
                    if done[rank] is None:
                        note(rank, f"killed at the {timeout}s deadline")
                        done[rank] = WorkerResult(rank, -9, collect(rank))
                break
            for rank in range(n_procs):
                if done[rank] is not None:
                    continue
                proc = procs[rank]
                if proc is None:
                    if now >= next_start[rank]:
                        start(rank)
                    continue
                rc = proc.poll()
                if rc is None:
                    if (
                        sup.liveness_timeout is not None
                        and time.time() - last_signal(rank) > sup.liveness_timeout
                    ):
                        proc.kill()
                        proc.wait()
                        on_crash(rank, proc.returncode, "went silent")
                    continue
                if rc == 0:
                    done[rank] = WorkerResult(rank, 0, collect(rank))
                else:
                    on_crash(rank, rc, "crashed")
            if any(d is None for d in done):
                time.sleep(sup.poll_interval)
    finally:
        for proc in procs:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
        for f in outs:
            f.close()

    results = [d for d in done if d is not None]
    failed = [r for r in results if r.returncode != 0]
    if failed:
        tails = "\n".join(
            f"--- worker {r.process_id} (rc={r.returncode}) ---\n{r.output[-4000:]}"
            for r in failed
        )
        raise RuntimeError(
            f"{len(failed)}/{len(results)} supervised workers failed "
            f"(restart budget {sup.max_restarts}/rank):\n{tails}"
        )
    return results


# --------------------------------------------------------------------------
# LatticeRecords ↔ npz (the parity harness compares across processes)
# --------------------------------------------------------------------------


def save_records(path: str, records, meta: dict) -> None:
    """Persist a ``LatticeRecords`` (+ run metadata) to one ``.npz``."""
    np.savez(
        path,
        __axes__=json.dumps(records.axes),
        __meta__=json.dumps(meta),
        eval_rounds=records.eval_rounds,
        **{f: getattr(records, f) for f in _RECORD_FIELDS},
    )


def load_records(path: str):
    """Inverse of :func:`save_records` → ``(LatticeRecords, meta)``."""
    from repro.sim.lattice import LatticeRecords

    with np.load(path) as z:
        axes = json.loads(str(z["__axes__"]))
        meta = json.loads(str(z["__meta__"]))
        records = LatticeRecords(
            axes=axes,
            eval_rounds=z["eval_rounds"],
            **{f: z[f] for f in _RECORD_FIELDS},
        )
    return records, meta


# --------------------------------------------------------------------------
# the parity workload — ONE task definition shared by the subprocess workers
# and the in-process reference run, so the harness compares like for like
# --------------------------------------------------------------------------


def _parity_loss_fn(params, x, y):
    import jax
    import jax.numpy as jnp

    logits = x @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def parity_spec(n_rounds: int = 4):
    """The pinned 2-policy × 2-noise × 3-seed grid (6 cells per policy —
    deliberately NOT a multiple of the 8-device CI topology, so the parity
    run exercises dead-cell padding across the process boundary)."""
    from repro.sim.lattice import LatticeSpec

    return LatticeSpec(
        policies=("pofl", "channel"),
        noise_powers=(1e-11, 1e-9),
        alphas=(0.1,),
        seeds=(0, 1000, 2000),
        n_rounds=n_rounds,
        eval_every=2,
    )


def run_parity_lattice(mesh=None, n_rounds: int = 4):
    """Run the parity workload twice on one engine → ``(records, meta)``.

    The second call must re-trace nothing (``n_lattice_traces`` flat) — the
    acceptance retrace guard runs INSIDE the worker topology, where the
    trace is the expensive multi-process SPMD program. Since the
    policy-fused lattice, the whole multi-policy spec is ONE engine (the
    ``FUSED_POLICY`` cache sentinel), ONE trace, and ONE compile — and the
    ``fuse_policies=False`` per-policy fallback must reproduce its records
    bit for bit on the same topology (``fused_matches_fallback``), with the
    cell axis now spanning policies across the process boundary.
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from repro.core.pofl import POFLConfig
    from repro.data.partition import partition_noniid_shards
    from repro.data.synthetic import make_classification_dataset
    from repro.sim.engine import FUSED_POLICY, cached_engine
    from repro.sim.lattice import run_lattice

    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 640, key)
    data = partition_noniid_shards(x, y, n_devices=8)
    params0 = {"w": jnp.zeros((784, 10)), "b": jnp.zeros((10,))}

    def eval_fn(p):
        logits = x[:200] @ p["w"] + p["b"]
        return (
            _parity_loss_fn(p, x[:200], y[:200]),
            jnp.mean(jnp.argmax(logits, -1) == y[:200]),
        )

    spec = parity_spec(n_rounds)
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    kw = dict(base_cfg=cfg, eval_fn=eval_fn, mesh=mesh)
    records = run_lattice(_parity_loss_fn, data, params0, spec, **kw)

    def fused_engine():
        return cached_engine(
            _parity_loss_fn, data, _dc.replace(cfg, policy=FUSED_POLICY),
            eval_fn=eval_fn, mesh=mesh,
        )

    traces = fused_engine().n_lattice_traces
    n_compiles = fused_engine().n_compiles
    repeat = run_lattice(_parity_loss_fn, data, params0, spec, **kw)
    traces_after = fused_engine().n_lattice_traces
    repeat_exact = all(
        np.array_equal(getattr(records, f), getattr(repeat, f))
        for f in _RECORD_FIELDS
    )
    fallback = run_lattice(
        _parity_loss_fn, data, params0, spec, fuse_policies=False, **kw
    )
    fused_matches_fallback = all(
        np.array_equal(getattr(records, f), getattr(fallback, f))
        for f in _RECORD_FIELDS
    )
    meta = {
        "n_rounds": n_rounds,
        "traces_first": traces,
        "n_lattice_compiles": n_compiles,
        "retrace_delta": int(traces_after - traces),
        "repeat_exact": bool(repeat_exact),
        "fused_matches_fallback": bool(fused_matches_fallback),
    }
    return records, meta


# --------------------------------------------------------------------------
# worker entrypoints
# --------------------------------------------------------------------------


def _worker_parity(args) -> None:
    from repro.sim.compile_cache import enable_compile_cache
    from repro.sim.multihost import initialize_distributed, make_global_cell_mesh

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR inherited from the launcher
    initialize_distributed()
    import jax

    # no ambient-mesh context needed: run_lattice places everything with
    # explicit NamedShardings (the `-- command` test runs the same lattice
    # with no mesh context at all)
    mesh = make_global_cell_mesh()
    records, meta = run_parity_lattice(mesh=mesh, n_rounds=args.n_rounds)
    meta.update(
        process_count=jax.process_count(),
        process_index=jax.process_index(),
        n_global_devices=len(jax.devices()),
        n_local_devices=len(jax.local_devices()),
    )
    print(f"[worker {jax.process_index()}] {meta}", flush=True)
    if jax.process_index() == 0 and args.out:
        save_records(args.out, records, meta)


def _worker_bench(args) -> None:
    import time

    from repro.sim.compile_cache import enable_compile_cache
    from repro.sim.multihost import initialize_distributed, make_global_cell_mesh

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR inherited from the launcher
    initialize_distributed()
    import jax

    from benchmarks.common import bench_sweep  # parent cwd is on PYTHONPATH
    from repro.sim import engine_cache_stats

    mesh = make_global_cell_mesh()
    t0 = time.time()
    _, timings, cells = bench_sweep(
        backend=args.backend, mesh=mesh, n_rounds=args.n_rounds
    )
    cache = engine_cache_stats()
    payload = {
        "lattice_seconds": round(timings["cold_seconds"], 3),
        "steady_seconds": round(timings["steady_seconds"], 3),
        "compile_seconds": round(timings["compile_seconds"], 3),
        "n_compiles": timings["n_compiles"],
        "engine_cache_hits": cache["hits"],
        "engine_cache_misses": cache["misses"],
        "wall_seconds": round(time.time() - t0, 3),
        "cells": cells,
        "n_hosts": jax.process_count(),
        "mesh_devices": len(jax.devices()),
    }
    print(f"[worker {jax.process_index()}] bench {payload}", flush=True)
    if jax.process_index() == 0 and args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)


# --------------------------------------------------------------------------
# the resilient workload — independent rank-sharded checkpointed sweep
# (the supervised counterpart of the parity workload: no collectives, so a
# crashed rank restarts alone and resumes from its own checkpoints)
# --------------------------------------------------------------------------


def resilient_spec(n_rounds: int = 6):
    """The pinned fault-injection grid: 2 policies × 2 seeds × 2 local
    algorithms (fedavg + the stateful feddyn, so a resumed carry includes
    ``AlgState``) over the churn scenario — 8 cells, split across ranks."""
    from repro.sim.lattice import LatticeSpec

    return LatticeSpec(
        policies=("pofl", "channel"),
        noise_powers=(1e-11,),
        alphas=(0.1,),
        seeds=(0, 1000),
        n_rounds=n_rounds,
        eval_every=2,
        algorithms=("fedavg", "feddyn"),
    )


def _resilient_task():
    """One small fixed task for every resilient worker: dirichlet_mixed
    non-iid partition (unequal true shard sizes ride in ``n_samples``)."""
    import jax
    import jax.numpy as jnp

    from repro.data.partition import partition_dirichlet_mixed
    from repro.data.synthetic import make_classification_dataset

    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 320, key, dim=16)
    data = partition_dirichlet_mixed(x, y, n_devices=8, seed=0)
    params0 = {"w": jnp.zeros((16, 10)), "b": jnp.zeros((10,))}
    return _parity_loss_fn, data, params0


def _worker_resilient(args) -> None:
    """Run THIS rank's shard of the resilient sweep (rank/count from the
    ``REPRO_DIST_*`` env), checkpointing every ``--checkpoint-every`` rounds
    under ``--checkpoint-dir`` and publishing ``shard-r<rank>.npz`` there.
    Independent per rank: never calls ``initialize_distributed``."""
    from repro.core.pofl import POFLConfig
    from repro.obs.sink import process_coords
    from repro.sim.resilience import fault_nan, run_worker_shard

    loss_fn, data, params0 = _resilient_task()
    spec = resilient_spec(args.n_rounds)
    cfg = POFLConfig(
        n_devices=8, n_scheduled=3,
        # quarantine only when a NaN fault is injected: the default run
        # keeps the zero-overhead propagate path
        on_nonfinite="skip" if fault_nan() is not None else "propagate",
    )
    rank, _ = process_coords()
    shard_out = os.path.join(args.checkpoint_dir, f"shard-r{rank}.npz")
    lo, hi = run_worker_shard(
        loss_fn, data, params0, spec, shard_out,
        args.checkpoint_dir, args.checkpoint_every,
        base_cfg=cfg, scenario="churn",
    )
    print(f"[worker {rank}] shard cells [{lo}, {hi}) -> {shard_out}", flush=True)


def run_resilient(
    n_procs: int,
    checkpoint_dir: str,
    out: str = "",
    n_rounds: int = 6,
    checkpoint_every: int = 2,
    timeout: float = 900.0,
    supervisor: SupervisorConfig | None = None,
):
    """Supervise the resilient workload across ``n_procs`` independent local
    workers, then merge their shards into one full-grid ``LatticeRecords``
    (written to ``out`` as npz when given). Survives injected/real rank
    crashes up to the per-rank restart budget."""
    from repro.sim.resilience import merge_shards

    os.makedirs(checkpoint_dir, exist_ok=True)
    supervise_workers(
        [
            sys.executable, "-m", "repro.launch.distributed", "--worker",
            "--workload", "resilient",
            "--n-rounds", str(n_rounds),
            "--checkpoint-dir", checkpoint_dir,
            "--checkpoint-every", str(checkpoint_every),
        ],
        n_procs=n_procs,
        devices_per_proc=1,
        timeout=timeout,
        supervisor=supervisor,
    )
    spec = resilient_spec(n_rounds)
    records = merge_shards(
        spec, [os.path.join(checkpoint_dir, f"shard-r{r}.npz")
               for r in range(n_procs)],
    )
    if out:
        save_records(out, records, {"n_rounds": n_rounds, "n_procs": n_procs,
                                    "workload": "resilient"})
    return records


def run_bench(
    n_procs: int,
    devices_per_proc: int,
    backend: str = "jnp",
    n_rounds: int = 30,
    timeout: float = 1200.0,
) -> dict:
    """Spawn the bench workload across ``n_procs`` local hosts and return
    process 0's timing payload (used by ``benchmarks/run.py --hosts N``)."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "bench.json")
        run_workers(
            [
                sys.executable, "-m", "repro.launch.distributed", "--worker",
                "--workload", "bench", "--out", out,
                "--backend", backend, "--n-rounds", str(n_rounds),
            ],
            n_procs=n_procs,
            devices_per_proc=devices_per_proc,
            timeout=timeout,
        )
        with open(out) as f:
            return json.load(f)


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--" in argv:
        split = argv.index("--")
        argv, command = argv[:split], argv[split + 1:]
    else:
        command = None

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--procs", type=int, default=2, metavar="N",
                        help="number of coordinated local processes")
    parser.add_argument("--devices-per-proc", type=int, default=4, metavar="K",
                        help="fake CPU devices per process "
                        "(--xla_force_host_platform_device_count)")
    parser.add_argument("--workload", default="parity",
                        choices=("parity", "bench", "resilient"),
                        help="built-in workload when no `-- command` is given")
    parser.add_argument("--out", default="",
                        help="worker-0 output path (npz for parity, json for bench)")
    parser.add_argument("--n-rounds", type=int, default=4)
    parser.add_argument("--backend", default="jnp")
    parser.add_argument("--timeout", type=float, default=900.0)
    parser.add_argument("--checkpoint-dir", default="",
                        help="resilient workload: checkpoint/shard directory "
                        "(default: a temp dir)")
    parser.add_argument("--checkpoint-every", type=int, default=2,
                        help="resilient workload: rounds per checkpoint chunk")
    parser.add_argument("--max-restarts", type=int, default=2,
                        help="supervisor: restart budget per rank")
    parser.add_argument("--liveness-timeout", type=float, default=None,
                        help="supervisor: seconds of heartbeat silence "
                        "(REPRO_OBS_DIR mtimes) before a rank is killed and "
                        "restarted")
    parser.add_argument("--worker", action="store_true",
                        help=argparse.SUPPRESS)  # internal: run AS a worker
    args = parser.parse_args(argv)

    if args.worker:
        if args.workload == "parity":
            _worker_parity(args)
        elif args.workload == "resilient":
            _worker_resilient(args)
        else:
            _worker_bench(args)
        return

    if args.procs < 1:
        parser.error("--procs must be >= 1")
    if args.devices_per_proc < 1:
        parser.error("--devices-per-proc must be >= 1")

    if args.workload == "resilient" and command is None:
        import tempfile

        ckpt_dir = args.checkpoint_dir or tempfile.mkdtemp(prefix="repro-ckpt-")
        records = run_resilient(
            n_procs=args.procs,
            checkpoint_dir=ckpt_dir,
            out=args.out,
            n_rounds=args.n_rounds,
            checkpoint_every=args.checkpoint_every,
            timeout=args.timeout,
            supervisor=SupervisorConfig(
                max_restarts=args.max_restarts,
                liveness_timeout=args.liveness_timeout,
            ),
        )
        print(f"[launcher] resilient sweep done: {records.e_com.shape} "
              f"(checkpoints under {ckpt_dir})")
        return

    worker_argv = command or [
        sys.executable, "-m", "repro.launch.distributed", "--worker",
        "--workload", args.workload, "--out", args.out,
        "--n-rounds", str(args.n_rounds), "--backend", args.backend,
    ]
    results = run_workers(
        worker_argv,
        n_procs=args.procs,
        devices_per_proc=args.devices_per_proc,
        timeout=args.timeout,
    )
    sys.stdout.write(results[0].output)


if __name__ == "__main__":
    main()
