"""Benchmark aggregator: one entry per paper table/figure + kernel
micro-benchmarks + the roofline table + the sim-lattice throughput bench.

Prints ``name,us_per_call,derived`` CSV lines (reduced settings — pass
--full to the individual modules for paper-scale runs), and writes
``BENCH_sim.json`` so future PRs have a perf trajectory. Every run is ALSO
appended — stamped with the git SHA and a UTC timestamp — to
``BENCH_history.jsonl`` next to it, so the trajectory survives the
overwrite (``python -m benchmarks.report`` renders it).

``BENCH_sim.json`` schema (one flat object):
  cells, n_rounds, n_devices       — sweep size (cells = algorithms ×
                                     policies × trials)
  backend                          — aggregation backend ("jnp"/"pallas_fused")
  task                             — model the lattice trained ("logreg" =
                                     the historical MNIST-shaped logistic
                                     regression; "cnn" = the CIFAR-shaped
                                     4-conv CNN, --task cnn). Part of the
                                     perf-gate key: CNN throughput is never
                                     compared against logreg entries (legacy
                                     history rows without the field gate
                                     only against each other)
  algorithms                       — local-update algorithms the lattice
                                     swept (``core.local_update.ALGORITHMS``
                                     names; ["fedavg"] = the historical
                                     single-algorithm bench); >1 name folds
                                     the traced algorithm axis into the same
                                     single compile (--algorithms a,b)
  local_steps                      — local SGD steps per device per round
                                     (1 = the historical single-gradient
                                     round; --local-steps K)
  mesh_devices                     — devices the cell axis was sharded over
                                     (1 = unsharded run; with --hosts N this
                                     is the GLOBAL process-spanning count)
  mesh_shape                       — "CxM" string of the (cells, model) mesh
                                     the lattice ran on ("1x1" = unsharded,
                                     "Nx1" = the 1-D cell sharding, "CxM"
                                     with M > 1 = the 2-D model-sharded
                                     mesh); the perf-gate key alongside
                                     backend
  per_device_hbm_bytes             — argument+output+temp bytes of the
                                     compiled lattice program PER DEVICE
                                     (XLA ``memory_analysis`` via
                                     ``sim.engine.lattice_memory_stats``;
                                     0 when unavailable, e.g. --hosts > 1).
                                     Shrinks as the model axis grows at
                                     fixed D — the 2-D mesh's headline
                                     number
  dim                              — flat model dimension D of the bench
                                     task's params (7850 for the default
                                     784-dim logreg; --dim overrides the
                                     feature dimension)
  n_hosts                          — jax.distributed process count the
                                     lattice ran across (1 = single-host)
  lattice_seconds / loop_seconds   — COLD lattice (trace + compile + run) vs
                                     cached-engine run_pofl loop (the loop
                                     baseline always runs single-host,
                                     unsharded)
  steady_seconds                   — identical repeat lattice call (cached
                                     engine + AOT executable: zero retraces,
                                     zero recompiles — pure run)
  compile_seconds                  — AOT ``lower().compile()`` wall time
                                     inside the cold call
                                     (``sim.engine.lattice_compile_stats``)
  n_compiles                       — distinct lattice programs compiled
                                     (1: the whole policy-fused sweep is one
                                     program; was one per policy before)
  speedup                          — loop_seconds / steady_seconds (honest
                                     steady-state lattice vs cached loop)
  cold_speedup                     — loop_seconds / lattice_seconds (the old
                                     compile-blended number, kept for the
                                     trajectory)
  cells_per_sec                    — cells / lattice_seconds (cold, blended —
                                     the historical trajectory number)
  steady_cells_per_sec             — cells / steady_seconds
  round_cells_per_sec              — cells × n_rounds / lattice_seconds
  per_device_cells_per_sec         — steady_cells_per_sec / mesh_devices (the
                                     sharding-efficiency trajectory number;
                                     steady-state since the one-compile PR)
  per_host_cells_per_sec           — steady_cells_per_sec / n_hosts (the
                                     multi-host scaling trajectory number)
  engine_cache_hits / _misses      — engine cache counters over the lattice
                                     cold+warm pair (misses == 1: one fused
                                     engine per lattice; with --hosts N they
                                     come from worker 0, where the lattice
                                     engines live)

XLA compiles persist across runs (``repro.sim.compile_cache``) in
``$JAX_COMPILATION_CACHE_DIR`` when set, else in the checkout's fixed
``.jax_cache``: a repeat cold run then reloads every lattice program from
disk instead of recompiling (compile_seconds collapses to the
deserialization cost).

The process exits non-zero when any phase fails; the failing phase's CSV
line carries ``ERROR:<type>:<message>``. ``--hosts H > 1`` spawns CPU
workers through ``repro.launch.distributed`` and so needs
``JAX_PLATFORMS=cpu``.

``--backend {jnp,pallas_fused}`` selects the aggregation backend and
``--mesh N`` shards the lattice's cell axis over the first N local devices
(on CPU, export ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
first), both threaded through benchmarks/common.py. ``--mesh CxM`` (e.g.
``--mesh 4x2``) builds the 2-D ``("cells", "model")`` mesh instead — C
cell shards × M model shards per cell (``sim.lattice.make_cell_model_mesh``).
A ``--mesh`` exceeding the visible local device count is a HARD ERROR
(exit 2) — never a silent fall back to fewer devices. ``--dim D`` overrides
the bench task's feature dimension (D-scaling axis; 0 = the default 784)
and ``--sim-only`` runs just the sim-lattice bench (the perf-gate CI step).

``--hosts H`` (H > 1) measures the MULTI-HOST lattice instead: the sweep is
dispatched through ``repro.launch.distributed`` as H coordinated
``jax.distributed`` processes × (mesh/H) fake CPU devices each (no XLA_FLAGS
needed — the launcher sets each worker's pool), e.g.

    PYTHONPATH=src python -m benchmarks.run --hosts 2 --mesh 8

times the identical ``benchmarks.common.bench_sweep`` workload on a
2-process × 4-devices-per-process global mesh; ``--mesh`` must divide evenly
by ``--hosts`` (default: one device per host).
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
import traceback

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
HISTORY_PATH = os.path.join(_REPO_ROOT, "BENCH_history.jsonl")


def _git_sha() -> str:
    """The current commit SHA, or "unknown" outside a usable git checkout."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=_REPO_ROOT, capture_output=True, text=True, timeout=10,
            check=True,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 - no git, not a repo, timeout: all "unknown"
        return "unknown"


def append_history(payload: dict, path: str = HISTORY_PATH) -> dict:
    """Append one timestamped+SHA-stamped bench record to the history JSONL.

    ``BENCH_sim.json`` is overwritten per run (latest-state contract);
    this file is the append-only trajectory behind it.
    """
    entry = {
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "git_sha": _git_sha(),
        **payload,
    }
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(entry) + "\n")
    return entry


def _csv(name: str, seconds: float, derived: str):
    print(f"CSV,{name},{seconds*1e6:.0f},{derived}", flush=True)


def _run(name: str, fn, derive) -> bool:
    """Run one phase and print its CSV line; False when it raised (the
    remaining phases still run, and ``main`` exits non-zero at the end)."""
    t0 = time.time()
    try:
        out = fn()
        _csv(name, time.time() - t0, derive(out))
        return True
    except Exception as e:  # noqa: BLE001 - the boundary that reports a phase
        traceback.print_exc()
        _csv(name, time.time() - t0, f"ERROR:{type(e).__name__}:{e}")
        return False


def _kernel_micro():
    """Interpret-mode kernel sanity micro-bench (CPU: correctness-path only)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.aircomp import aircomp_fused, aircomp_fused_ref
    from repro.kernels.attention import flash_attention, mha_ref
    from repro.kernels.ssd import ssd_naive, ssd_pallas

    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (30, 4096))
    coeff = jnp.ones((30,)) / 30
    z = jnp.zeros((4096,))
    got = aircomp_fused(g, coeff, jnp.float32(0.1), jnp.float32(1.0),
                        jnp.float32(2.0), z, interpret=True)
    want = aircomp_fused_ref(g, coeff, jnp.float32(0.1), jnp.float32(1.0),
                             jnp.float32(2.0), z)
    err_a = float(jnp.max(jnp.abs(got - want)))

    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    fa = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    err_f = float(jnp.max(jnp.abs(fa - mha_ref(q, k, v))))

    xdt = jax.random.normal(ks[3], (1, 64, 2, 16))
    la = -jnp.abs(jax.random.normal(ks[0], (1, 64, 2))) - 0.1
    B = jax.random.normal(ks[1], (1, 64, 8))
    C = jax.random.normal(ks[2], (1, 64, 8))
    sp = ssd_pallas(xdt, la, B, C, chunk=16, interpret=True)
    err_s = float(jnp.max(jnp.abs(sp - ssd_naive(xdt, la, B, C))))
    assert max(err_a, err_f, err_s) < 1e-3
    return f"max_abs_err={max(err_a, err_f, err_s):.2e}"


def _bench_sim(
    backend: str = "jnp",
    mesh_devices: int = 0,
    n_hosts: int = 1,
    model_shards: int = 1,
    dim: int = 0,
    algorithms: tuple = ("fedavg",),
    local_steps: int = 1,
    task_name: str = "logreg",
    checkpoint_every: int = 0,
):
    """Reduced fig4-style sweep (5 policies × 3 trials) through sim.lattice
    vs the cached-engine one-run_pofl-per-cell loop → BENCH_sim.json.

    ``task_name`` selects the model trained in every cell (``--task``):
    ``"logreg"`` is the historical 784-dim bench, ``"cnn"`` the CIFAR-shaped
    4-conv CNN — it lands in the payload (and so in the perf-gate key), so
    the two workloads' throughput trajectories never cross-compare.

    The lattice runs TWICE (cold, then an identical warm repeat), splitting
    ``lattice_seconds``/``compile_seconds`` from ``steady_seconds`` so
    compile cost stops blending into throughput; ``loop_seconds`` is the
    PR-2 optimized wrapper (engine cache + single-static-length active-mask
    scan), so ``speedup`` is the honest steady-lattice-vs-loop number and
    ``cold_speedup`` the old blended one. ``mesh_devices > 0`` shards the
    lattice's cell axis over that many local devices; ``model_shards > 1``
    additionally shards the model dimension (``--mesh CxM`` → a 2-D
    ``make_cell_model_mesh(C, M)`` mesh). ``dim > 0`` overrides the bench
    task's feature dimension. ``n_hosts > 1`` instead runs the lattice
    across that many coordinated ``jax.distributed`` processes via the
    ``repro.launch.distributed`` launcher (``mesh_devices`` then counts the
    GLOBAL devices; 1-D only). The loop baseline always runs single-host,
    unsharded.
    """
    from benchmarks.common import (
        BENCH_SWEEP_KW, POLICIES, bench_sweep, bench_task, run_policies_loop,
        timed,
    )
    from repro.sim import (
        engine_cache_stats,
        lattice_memory_stats,
        make_cell_mesh,
        make_cell_model_mesh,
        reset_engine_cache,
    )

    n_rounds = BENCH_SWEEP_KW["n_rounds"]
    # shared between the lattice sweep and loop baseline
    task_kind = {"logreg": "mnist", "cnn": "cifar"}[task_name]
    task = bench_task(dim=dim or None, kind=task_kind)
    from jax.flatten_util import ravel_pytree

    flat_dim = int(ravel_pytree(task.params0)[0].size)
    mem_stats = {"per_device_hbm_bytes": 0}
    if n_hosts > 1:
        from repro.launch.distributed import run_bench

        total = mesh_devices or n_hosts
        worker = run_bench(
            n_procs=n_hosts,
            devices_per_proc=total // n_hosts,
            backend=backend,
            n_rounds=n_rounds,
        )
        timings = {
            "cold_seconds": worker["lattice_seconds"],
            "steady_seconds": worker["steady_seconds"],
            "compile_seconds": worker["compile_seconds"],
            "n_compiles": worker["n_compiles"],
        }
        lattice_cache = {
            "hits": worker["engine_cache_hits"],
            "misses": worker["engine_cache_misses"],
        }
        cells = worker["cells"]
        n_mesh = worker["mesh_devices"]
        mesh_shape = f"{n_mesh}x1"
    else:
        if model_shards > 1:
            cells_ax = mesh_devices // model_shards
            mesh = make_cell_model_mesh(cells_ax, model_shards)
            mesh_shape = f"{cells_ax}x{model_shards}"
        elif mesh_devices:
            mesh = make_cell_mesh(mesh_devices)
            mesh_shape = f"{mesh_devices}x1"
        else:
            mesh = None
            mesh_shape = "1x1"
        n_mesh = 1 if mesh is None else mesh_devices
        _, timings, cells = bench_sweep(
            backend=backend, mesh=mesh, task=task,
            algorithms=algorithms, local_steps=local_steps,
        )
        lattice_cache = engine_cache_stats()
        # capture the per-device HBM footprint BEFORE the cache reset below
        # evicts the engines holding the compiled executables
        mem_stats = lattice_memory_stats()
    t_cold = timings["cold_seconds"]
    t_steady = timings["steady_seconds"]
    # --checkpoint-every: additionally time the SAME sweep through the
    # resilient chunked runner (repro.sim.resilience) — its own chunk
    # programs, so a cold and a warm pass — and record the checkpoint
    # overhead next to the primary timings. The primary (unchunked)
    # steady_cells_per_sec is untouched, so perf-gate keys stay comparable.
    ckpt_payload = {}
    if checkpoint_every:
        import tempfile

        from benchmarks.common import sweep_lattice

        ck_kw = dict(
            BENCH_SWEEP_KW, policies=POLICIES, backend=backend,
            algorithms=algorithms, local_steps=local_steps,
            checkpoint_every=checkpoint_every,
        )
        with tempfile.TemporaryDirectory() as td:
            # distinct dirs: the warm pass must re-run, not resume the cold
            _, t_ck_cold = timed(
                sweep_lattice, task,
                checkpoint_dir=os.path.join(td, "cold"), **ck_kw,
            )
            _, t_ck = timed(
                sweep_lattice, task,
                checkpoint_dir=os.path.join(td, "warm"), **ck_kw,
            )
        ckpt_payload = {
            "checkpoint_every": checkpoint_every,
            "checkpointed_seconds": round(t_ck, 3),
            "checkpointed_cold_seconds": round(t_ck_cold, 3),
            "checkpoint_overhead": round(t_ck / t_steady - 1.0, 3),
        }
    reset_engine_cache()
    # the loop baseline runs the IDENTICAL workload (same algorithms ×
    # policies × trials grid, same local_steps) so `speedup` stays honest
    kw = dict(
        BENCH_SWEEP_KW, policies=POLICIES, backend=backend,
        algorithms=algorithms, local_steps=local_steps,
    )
    _, t_loop = timed(run_policies_loop, task, **kw)

    payload = {
        "cells": cells,
        "n_rounds": n_rounds,
        "n_devices": 20,
        "backend": backend,
        "task": task_name,
        "algorithms": list(algorithms),
        "local_steps": local_steps,
        "mesh_devices": n_mesh,
        "mesh_shape": mesh_shape,
        "per_device_hbm_bytes": int(mem_stats["per_device_hbm_bytes"]),
        "dim": flat_dim,
        "n_hosts": n_hosts,
        "lattice_seconds": round(t_cold, 3),
        "steady_seconds": round(t_steady, 3),
        "compile_seconds": round(timings["compile_seconds"], 3),
        "n_compiles": timings["n_compiles"],
        "loop_seconds": round(t_loop, 3),
        "speedup": round(t_loop / t_steady, 2),
        "cold_speedup": round(t_loop / t_cold, 2),
        "cells_per_sec": round(cells / t_cold, 3),
        "steady_cells_per_sec": round(cells / t_steady, 3),
        "round_cells_per_sec": round(cells * n_rounds / t_cold, 1),
        "per_device_cells_per_sec": round(cells / t_steady / n_mesh, 3),
        "per_host_cells_per_sec": round(cells / t_steady / n_hosts, 3),
        "engine_cache_hits": lattice_cache["hits"],
        "engine_cache_misses": lattice_cache["misses"],
        **ckpt_payload,
    }
    out_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_sim.json")
    with open(os.path.abspath(out_path), "w") as f:
        json.dump(payload, f, indent=2)
    append_history(payload)
    return payload


def main(argv: list[str] | None = None) -> None:
    from repro.core import BACKENDS
    from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    # persist every XLA compile below across runs; must precede the first
    # compile to catch them all
    enable_compile_cache(CHECKOUT_CACHE_DIR)

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--backend", default="jnp", choices=BACKENDS,
        help="aggregation backend for the sim-lattice bench",
    )
    parser.add_argument(
        "--mesh", type=str, default="0", metavar="N|CxM",
        help="shard the sim-lattice bench's cell axis over the first N local "
        "devices, or over a 2-D CxM (cells × model) mesh, e.g. --mesh 4x2 "
        "(0 = unsharded; on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=<total> first); "
        "with --hosts H this is the GLOBAL device count split H ways (1-D "
        "only)",
    )
    parser.add_argument(
        "--algorithms", type=str, default="fedavg", metavar="A[,B...]",
        help="comma-separated local-update algorithms for the sim-lattice "
        "bench (repro.core.local_update.ALGORITHMS names; >1 name sweeps "
        "the traced algorithm axis inside the same single compile); "
        "unknown or empty names are a hard error",
    )
    parser.add_argument(
        "--local-steps", type=int, default=1, metavar="K",
        help="local SGD steps per device per round for the sim-lattice "
        "bench (1 = the historical single-gradient round)",
    )
    parser.add_argument(
        "--task", default="logreg", choices=("logreg", "cnn"),
        help="model the sim-lattice bench trains: logreg (the historical "
        "784-dim task) or cnn (CIFAR-shaped 4-conv CNN, D≈2.6e5); recorded "
        "as `task` in BENCH_sim.json / BENCH_history.jsonl so the perf gate "
        "never compares the two workloads",
    )
    parser.add_argument(
        "--dim", type=int, default=0, metavar="D",
        help="override the bench task's feature dimension (0 = the default "
        "784-dim task; the flat model dimension lands in BENCH_sim.json "
        "as `dim`)",
    )
    parser.add_argument(
        "--checkpoint-every", type=int, default=0, metavar="K",
        help="additionally time the sim-lattice sweep through the resilient "
        "chunked runner (repro.sim.resilience), checkpointing the carry "
        "every K rounds; records checkpointed_seconds/checkpoint_overhead "
        "in BENCH_sim.json (0 = off; single-host, unsharded only)",
    )
    parser.add_argument(
        "--sim-only", action="store_true",
        help="run only the sim-lattice bench (the perf-gate CI step): "
        "writes BENCH_sim.json + BENCH_history.jsonl and skips the "
        "figure/kernel/roofline benches",
    )
    parser.add_argument(
        "--hosts", type=int, default=1, metavar="H",
        help="run the sim-lattice bench across H coordinated jax.distributed "
        "processes via repro.launch.distributed (1 = in-process)",
    )
    args = parser.parse_args(argv)

    # validate the topology UP FRONT: a --mesh that cannot be honored must
    # abort the whole run (exit 2), not degrade into a CSV ERROR line while
    # every other benchmark silently proceeds without BENCH_sim.json
    if args.hosts < 1:
        parser.error(f"--hosts must be >= 1 (got {args.hosts})")
    if args.hosts > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        parser.error(
            "--hosts > 1 spawns CPU workers (repro.launch.distributed); "
            "run it with JAX_PLATFORMS=cpu"
        )
    # validate the algorithm axis UP FRONT too: a malformed --algorithms is a
    # hard parser error (exit 2), never a mid-run CSV ERROR line
    from repro.core.local_update import ALGORITHMS

    algorithms = tuple(s.strip() for s in args.algorithms.split(","))
    if not algorithms or any(not a for a in algorithms):
        parser.error(f"--algorithms must be a,b,... names (got {args.algorithms!r})")
    for a in algorithms:
        if a not in ALGORITHMS:
            parser.error(
                f"--algorithms: unknown algorithm {a!r}; choose from {ALGORITHMS}"
            )
    if args.local_steps < 1:
        parser.error(f"--local-steps must be >= 1 (got {args.local_steps})")
    if args.hosts > 1 and (algorithms != ("fedavg",) or args.local_steps != 1):
        parser.error("--algorithms/--local-steps are single-host only")
    if args.checkpoint_every < 0:
        parser.error(f"--checkpoint-every must be >= 0 (got {args.checkpoint_every})")
    if args.checkpoint_every and args.hosts > 1:
        parser.error("--checkpoint-every is single-host only")
    try:
        if "x" in args.mesh:
            cells_s, model_s = args.mesh.split("x")
            mesh_total, model_shards = int(cells_s) * int(model_s), int(model_s)
            if int(cells_s) < 1 or model_shards < 1:
                raise ValueError(args.mesh)
        else:
            mesh_total, model_shards = int(args.mesh), 1
    except ValueError:
        parser.error(f"--mesh must be an integer N or CxM (got {args.mesh!r})")
    if mesh_total < 0:
        parser.error(f"--mesh must be >= 0 (got {args.mesh})")
    if args.dim < 0:
        parser.error(f"--dim must be >= 0 (got {args.dim})")
    if args.task == "cnn" and args.dim:
        parser.error("--dim only applies to the logreg task (cnn input shape is fixed)")
    if args.task == "cnn" and args.hosts > 1:
        parser.error("--task cnn is single-host only")
    if model_shards > 1 and args.hosts > 1:
        parser.error("--mesh CxM (model sharding) is single-host only")
    if args.checkpoint_every and mesh_total:
        parser.error(
            "--checkpoint-every is unsharded only (the chunked runner owns "
            "its own placement); drop --mesh"
        )
    if args.hosts == 1 and mesh_total:
        import jax

        n_local = len(jax.devices())
        if mesh_total > n_local:
            parser.error(
                f"--mesh {args.mesh} needs {mesh_total} devices but only "
                f"{n_local} local device(s) are visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={mesh_total}"
            )
    if args.hosts > 1 and (mesh_total or args.hosts) % args.hosts:
        parser.error(
            f"--mesh {args.mesh} must divide evenly across --hosts {args.hosts}"
        )

    ok = []
    if not args.sim_only:
        ok.append(_run("kernels_microbench", _kernel_micro, lambda d: d))
    ok.append(_run(
        "sim_lattice",
        lambda: _bench_sim(
            backend=args.backend, mesh_devices=mesh_total,
            n_hosts=args.hosts, model_shards=model_shards, dim=args.dim,
            algorithms=algorithms, local_steps=args.local_steps,
            task_name=args.task, checkpoint_every=args.checkpoint_every,
        ),
        lambda d: (
            "steady_cells/s=%.2f cold_cells/s=%.2f compile_s=%.1f "
            "n_compiles=%d speedup=%.1fx backend=%s task=%s mesh=%s "
            "hbm/dev=%d dim=%d hosts=%d" % (
                d["steady_cells_per_sec"], d["cells_per_sec"],
                d["compile_seconds"], d["n_compiles"], d["speedup"],
                d["backend"], d["task"], d["mesh_shape"],
                d["per_device_hbm_bytes"], d["dim"], d["n_hosts"],
            )
        ),
    ))
    if not args.sim_only:
        ok += _paper_phases()
    if not all(ok):
        sys.exit(1)


def _paper_phases() -> list[bool]:
    """The figure/table reproductions and the roofline, one phase each."""
    from benchmarks import (
        fig3_single_device,
        fig4_multi_device,
        fig5_noise_power,
        fig6_num_devices,
        fig7_heterogeneity,
        roofline,
        table1_alpha,
    )

    phases = [
        ("fig3_single_device", fig3_single_device.main,
         lambda r: "pofl=%.3f noisefree=%.3f chan=%.3f" % (
             r["mnist"]["pofl"]["best_acc"],
             r["mnist"]["noisefree"]["best_acc"],
             r["mnist"]["channel"]["best_acc"],
         )),
        ("fig4_multi_device", fig4_multi_device.main,
         lambda r: "pofl=%.3f det=%.3f" % (
             r["mnist"]["pofl"]["best_acc"],
             r["mnist"]["deterministic"]["best_acc"],
         )),
        ("fig5_noise_power", fig5_noise_power.main,
         lambda r: "pofl@1e-9=%.3f chan@1e-9=%.3f" % (
             r[1e-9]["pofl"]["best_acc"], r[1e-9]["channel"]["best_acc"],
         )),
        ("fig6_num_devices", fig6_num_devices.main,
         lambda r: "pofl@S1=%.3f pofl@S10=%.3f pofl@S30=%.3f" % (
             r[1]["pofl"]["best_acc"], r[10]["pofl"]["best_acc"],
             r[30]["pofl"]["best_acc"],
         )),
        ("fig7_heterogeneity", fig7_heterogeneity.main,
         lambda r: "pofl@C1=%.3f pofl@C8=%.3f" % (
             r[1]["pofl"]["best_acc"], r[8]["pofl"]["best_acc"],
         )),
        ("table1_alpha", table1_alpha.main,
         lambda r: "; ".join(
             f"s={k:.0e}:best_a={max(v, key=v.get)}" for k, v in r.items()
         )),
        ("roofline", roofline.main,
         lambda air: "aircomp %s-bound on %s" % (air["dominant"], air["device_kind"])),
    ]
    return [_run(name, fn, derive) for name, fn, derive in phases]


if __name__ == "__main__":
    main()
