"""Run one benchmark cell once.

    python3 -m perfbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Everything a cell needs is found by name:
the cell in ``BENCHMARK.json``, its configuration in the file that entry
names, its traffic in ``perfbench/traffic/<traffic>.json`` and each
per-layer metric in ``perfbench/metrics/<metric>.py``.

One run is one process on the chips it holds. In order it

1. checks for a TPU with as many chips as the cell asks for, and exits 2
   with no result when there is none (no CPU fallback, no interpret mode);
2. keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` when
   set, else in ``<checkout>/.jax_cache``;
3. draws the configuration's dataset from its fixed ``data_seed``, and the
   initial weights from ``--seed`` on the device. The program bakes its
   dataset into the compiled lattice as a constant, so a dataset drawn
   from ``--seed`` would recompile every run; ``--seed`` drives the
   weights and every lattice seed (channels, mini-batches, scheduling
   draws, receiver noise) instead;
4. compiles the cell's one lattice program and runs one warm-up sweep
   (set-up ends here);
5. runs sweeps back to back until ``--seconds`` have passed; sweep ``k``
   takes its lattice seeds from ``(--seed, k)``, values the program vmaps,
   so nothing compiles in the window. With ``--trace 1`` the window runs
   under the profiler, each sweep inside a ``perfbench.sweep`` span and
   the harness's loop between sweeps inside ``perfbench.between``;
6. reads the peak device memory, frees the program's state, and checks
   the first rounds of the warm-up sweep and of every sweep of the window
   against the plain reference (``perfbench/reference.py``,
   ``perfbench/check.py``);
7. prints each number compared with its limit as the last lines of
   standard error, and one JSON object as the last line of standard
   output, naming the device.

A lattice sweep is a batch job: ``cell_rounds_per_s`` is every cell-round
of every sweep completed in the window over the window's whole wall time,
from the first sweep's dispatch to the last sweep's records on the host.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import itertools
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_ROUNDS = 3  # rounds of each sweep the reference follows


def load_cell(root: str, workload: str):
    """(benchmark, cell entry, configuration, traffic) for ``workload``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def load_metric(root: str, name: str):
    """The reader module of per-layer metric ``name``."""
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed32(*entropy) -> int:
    """A non-negative int32 drawn from any whole numbers (``--seed`` may
    exceed 32 bits)."""
    return int(np.random.SeedSequence(list(entropy)).generate_state(1)[0] & 0x7FFFFFFF)


def sweep_seeds(seed: int, k: int, n: int) -> tuple:
    """``n`` distinct lattice seeds of sweep ``k``."""
    state = np.random.SeedSequence([seed, k]).generate_state(4 * n) & 0x7FFFFFFF
    out = list(dict.fromkeys(int(s) for s in state))[:n]
    if len(out) < n:
        raise RuntimeError("sweep seeds collided")
    return tuple(out)


def cell_grid(traffic: dict, seeds: tuple) -> list:
    """(policy, noise power, alpha, seed) of each cell, in the lattice's
    flat order: policy-major, then noise, alpha, seed."""
    return list(itertools.product(
        traffic["policies"], traffic["noise_powers"], traffic["alphas"], seeds
    ))


class Program:
    """The system under test: the lattice of the cell, as users drive it."""

    def __init__(self, config: dict, traffic: dict, data, params0):
        import jax.numpy as jnp

        from repro.core.pofl import DeviceData, POFLConfig
        from repro.models import small
        from repro.sim import LatticeSpec, run_lattice
        from repro.sim.tasks import TaskEval

        fx, fy, x_te, y_te = data
        self._spec_cls = LatticeSpec
        self._run = run_lattice
        if config["task"] == "logreg":
            self.loss_fn, logits_fn = small.logreg_loss, small.logreg_logits
        else:
            self.loss_fn, logits_fn = small.cnn_loss, small.cnn_logits
        self.data = DeviceData(features=jnp.asarray(fx), labels=jnp.asarray(fy))
        self.eval_fn = TaskEval(logits_fn, x_te, y_te, batch=config["n_test"])
        self.cfg = POFLConfig(
            n_devices=config["n_devices"], n_scheduled=config["n_scheduled"],
            batch_size=config["batch_size"], local_steps=config["local_steps"],
            lr0=config["lr0"], lr_decay=config["lr_decay"], lr_min=config["lr_min"],
            tx_power=config["tx_power"], sampler=config["sampler"],
            simulate_physical=True, backend="pallas_fused",
        )
        self.traffic = traffic
        self.params0 = params0
        self.mesh = tuple(traffic["mesh"]) if traffic.get("mesh") else None

    def sweep(self, seeds: tuple):
        t = self.traffic
        spec = self._spec_cls(
            policies=tuple(t["policies"]), noise_powers=tuple(t["noise_powers"]),
            alphas=tuple(t["alphas"]), seeds=seeds, n_rounds=t["rounds"],
            eval_every=t["eval_every"],
        )
        return self._run(
            self.loss_fn, self.data, self.params0, spec, base_cfg=self.cfg,
            eval_fn=self.eval_fn, mesh=self.mesh,
        )


def first_rounds(recs) -> dict:
    """The records the check compares, flat over cells (lattice order)."""
    t = CHECK_ROUNDS
    out = {
        f: np.asarray(getattr(recs, f)).reshape(-1, np.shape(getattr(recs, f))[-1])[:, :t]
        for f in ("grad_norm", "e_com", "e_var", "n_scheduled")
    }
    for f in ("loss", "acc"):
        v = np.asarray(getattr(recs.eval, f))
        out[f"eval0_{f}"] = v.reshape(-1, v.shape[-1])[:, 0]
    return out


def enable_compile_cache(root: str) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def main(argv=None, *, t0: float | None = None, root: str = ROOT,
         require_tpu: bool = True, program_cls=Program) -> int:
    """One run. ``require_tpu=False`` and ``program_cls`` exist for the
    harness's own tests, which drive a run on the CPU at a tiny size, with
    the program broken underneath or the control in its place."""
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one perfbench cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"perfbench: no program at {src}/repro", file=sys.stderr)
        return 2
    bench, cell, config, traffic = load_cell(root, args.workload)
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(
            f"perfbench: {args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr,
        )
        return 2
    devices = devices[:chips]
    cache = enable_compile_cache(root)
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.obs.registry import metric_value

    from perfbench import check, data as bdata

    t_data = time.perf_counter()
    data = bdata.make_dataset(config)
    params0 = bdata.init_params(config, jax.random.PRNGKey(seed32(args.seed, 1 << 40)))
    prog = program_cls(config, traffic, data, params0)
    seeds = sweep_seeds(args.seed, 0, traffic["seeds"])
    n_cells = len(cell_grid(traffic, seeds))
    kept = []  # (seeds, first-round records) of every sweep run
    t_warm = time.perf_counter()
    kept.append((seeds, first_rounds(prog.sweep(seeds))))  # warm-up sweep
    setup_s = time.perf_counter() - t0
    compile_s = float(metric_value("lattice.compile_seconds"))
    compiles0 = int(metric_value("lattice.n_compiles"))
    log(f"device {devices[0].device_kind} x{len(devices)}; cache {cache}; "
        f"set-up {setup_s:.3f} s: start to data {t_data - t0:.3f}, data and "
        f"weights {t_warm - t_data:.3f}, warm-up sweep {time.perf_counter() - t_warm:.3f} "
        f"(of it compile or cache load {compile_s:.3f})")

    trace_dir = os.path.join(root, "chiprun_out", "perfbench", args.workload, "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    k = 1
    seeds = sweep_seeds(args.seed, k, traffic["seeds"])
    t_start = time.perf_counter()
    ends = [t_start]
    while True:
        with jax.profiler.TraceAnnotation("perfbench.sweep"):
            recs = prog.sweep(seeds)
        ends.append(time.perf_counter())
        window_s = ends[-1] - t_start
        with jax.profiler.TraceAnnotation("perfbench.between"):
            kept.append((seeds, first_rounds(recs)))
            k += 1
            seeds = sweep_seeds(args.seed, k, traffic["seeds"])
        if window_s >= args.seconds:
            break
    n_sweeps = k - 1
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = int(metric_value("lattice.n_compiles")) - compiles0
    rate = n_cells * traffic["rounds"] * n_sweeps / window_s
    # the TPU runtime reserves an executable's temporaries apart from the
    # buffers it allocates, so a chip's peak is the sum of the two peaks
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
               for st in stats)
    log(f"memory_stats {stats}")
    log(f"window {window_s:.3f} s, {n_sweeps} sweeps, {rate:.3f} cell-rounds/s, "
        f"{window_compiles} compiles, peak {peak} B; sweeps end to end "
        f"{[round(b - a, 3) for a, b in zip(ends, ends[1:])]} s")

    # free the program's state before the reference takes the chip
    from perfbench.reference import POLICIES, Reference, flatten, layout

    lay = layout(bdata.param_shapes(config))
    w0_flat = flatten(params0, lay)
    del prog, recs, params0
    from repro.sim import reset_engine_cache

    reset_engine_cache()
    gc.collect()

    t_ref = time.perf_counter()
    ref = Reference(config, *data, n_rounds=CHECK_ROUNDS)
    readings = []
    for seeds_k, got in kept:
        grid = cell_grid(traffic, seeds_k)
        want = ref.run(
            w0_flat, [g[3] for g in grid], [g[1] for g in grid],
            [g[2] for g in grid], [POLICIES.index(g[0]) for g in grid],
        )
        readings.append(check.compare(got, want, config["tie_margin"]))
    numbers = check.merge(readings)
    limits = config["limits"]
    correct, rows = check.verdict(numbers, limits)
    failed = sum(not check.verdict(r, limits)[0] for r in readings)
    log(f"reference over {len(kept)} sweeps in {time.perf_counter() - t_ref:.3f} s; "
        f"cells compared {numbers['cells_compared']}, left out as tied "
        f"{numbers['cells_tied']}")

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(peak),
    }
    result = {"correct": bool(correct), "attempted": len(kept), "failed": failed}
    if args.trace:
        from perfbench import trace as btrace

        files = [
            os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb")
        ]
        red = btrace.reduce_file(files[0]) if files else None
        ctx = SimpleNamespace(
            red=red, config=config, traffic=traffic, chips=chips,
            n_cells=n_cells, rate=rate, compile_s=compile_s,
            window_compiles=window_compiles, device_kind=devices[0].device_kind,
        )
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = load_metric(root, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red.mean(lambda d: d.busy_ns) / 1e9
            device["window_s"] = red.window_s
            result["breakdown"] = btrace.breakdown(red)
    else:
        metrics = {
            "cell_rounds_per_s": {"value": rate, "unit": "cell-rounds/s"},
            "peak_hbm_gib": {"value": peak / 2**30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result.update(metrics=metrics, device=device, checks=rows)
    log(f"correct = {correct}")
    for r in rows:
        print(f"check {r['name']} = {r['value']!r} (limit {r['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
