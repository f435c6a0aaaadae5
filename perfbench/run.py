"""Run one benchmark cell once.

    python3 -m perfbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Everything a cell needs is found by name:
the cell in ``BENCHMARK.json``, its configuration in the file that entry
names, its traffic in ``perfbench/traffic/<traffic>.json``, the program
it drives in ``perfbench/programs/<program>.py`` (the configuration's
``program`` key; ``lattice`` where it has none) and each per-layer metric
in ``perfbench/metrics/<metric>.py``. So a configuration that needs its
own program joins the benchmark as new files and entries only.

A program module supplies:

* ``make_data(config)``: the dataset, drawn from the configuration alone;
* ``init_params(config, key)``: the initial state, from the run's key;
* ``Program(config, traffic, data, params0)``, whose ``sweep(seeds)`` runs
  one unit of the timed work and returns its records. ``seeds`` holds the
  traffic's ``seeds`` distinct run seeds, drawn from ``--seed``;
* ``n_cells(traffic)``: the cells one sweep runs;
* ``cell_rounds_per_sweep(traffic)``: the cell-rounds one sweep completes,
  the unit of ``cell_rounds_per_s``;
* ``kept(records)``: what the check compares of a sweep's records;
* ``counters()``: (seconds spent compiling, programs compiled) so far,
  each None where the program does not count it;
* ``release()``: frees the program's cached state before the check;
* ``check(config, traffic, data, params0, kept_list)``: one reading per
  ``(seeds, kept)`` pair of ``kept_list``, a dict of the numbers that
  ``perfbench/check.py`` holds to the configuration's ``limits``, with
  ``cells_compared``, the answers compared (none is not correct);
* ``flops_per_cell_round(config, traffic)``: the model FLOPs of one
  cell-round, or None.

One run is one process on the chips it holds. In order it

1. checks for a TPU with as many chips as the cell asks for, and exits 2
   with no result when there is none (no CPU fallback, no interpret mode),
   and exits 2 when the configuration's program is missing or incomplete;
2. keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` when
   set, else in ``<checkout>/.jax_cache``;
3. makes the program's data and, from ``--seed``, its initial state;
4. builds the program and runs one warm-up sweep (set-up ends here);
5. runs sweeps back to back until ``--seconds`` have passed; sweep ``k``
   takes its run seeds from ``(--seed, k)``. With ``--trace 1`` the window
   runs under the profiler, each sweep inside a ``perfbench.sweep`` span
   and the harness's loop between sweeps inside ``perfbench.between``;
6. reads the peak device memory, frees the program's state, and checks
   the kept records of the warm-up sweep and of every sweep of the window
   with the program's check;
7. prints each number compared with its limit as the last lines of
   standard error, and one JSON object as the last line of standard
   output, naming the device.

A sweep is a batch job: ``cell_rounds_per_s`` is every cell-round of
every sweep completed in the window over the window's whole wall time,
from the first sweep's dispatch to the last sweep's records on the host.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROGRAM_NAMES = (
    "make_data", "init_params", "Program", "n_cells", "cell_rounds_per_sweep",
    "kept", "counters", "release", "check", "flops_per_cell_round",
)


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


def load_program(root: str, name: str):
    """The program module ``name``: ``perfbench/programs/<name>.py`` under
    ``root``. Raises ``LookupError`` naming what is missing."""
    path = os.path.join(root, "perfbench", "programs", name + ".py")
    if not os.path.isfile(path):
        raise LookupError(f"no program {name!r}: {path} does not exist")
    mod_name = f"perfbench_program_{name}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    missing = [n for n in PROGRAM_NAMES if not hasattr(mod, n)]
    if missing:
        raise LookupError(f"program {name!r} ({path}) lacks {', '.join(missing)}")
    return mod


def seed32(*entropy) -> int:
    """A non-negative int32 drawn from any whole numbers (``--seed`` may
    exceed 32 bits)."""
    return int(np.random.SeedSequence(list(entropy)).generate_state(1)[0] & 0x7FFFFFFF)


def sweep_seeds(seed: int, k: int, n: int) -> tuple:
    """``n`` distinct run seeds of sweep ``k``."""
    state = np.random.SeedSequence([seed, k]).generate_state(4 * n) & 0x7FFFFFFF
    out = list(dict.fromkeys(int(s) for s in state))[:n]
    if len(out) < n:
        raise RuntimeError("sweep seeds collided")
    return tuple(out)


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
         require_tpu: bool = True, program_cls=None) -> int:
    """One run. ``require_tpu=False`` and ``program_cls`` (in place of the
    program module's ``Program``) exist for the harness's own tests, which
    drive a run on the CPU at a tiny size, with the program broken
    underneath or the control in its place."""
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
    try:
        program = load_program(root, config.get("program", "lattice"))
    except LookupError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2

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
    from perfbench import check

    t_data = time.perf_counter()
    data = program.make_data(config)
    params0 = program.init_params(config, jax.random.PRNGKey(seed32(args.seed, 1 << 40)))
    prog = (program_cls or program.Program)(config, traffic, data, params0)
    seeds = sweep_seeds(args.seed, 0, traffic["seeds"])
    n_cells = program.n_cells(traffic)
    kept = []  # (seeds, kept records) of every sweep run
    t_warm = time.perf_counter()
    kept.append((seeds, program.kept(prog.sweep(seeds))))  # warm-up sweep
    setup_s = time.perf_counter() - t0
    compile_s, compiles0 = program.counters()
    log(f"device {devices[0].device_kind} x{len(devices)}; cache {cache}; "
        f"set-up {setup_s:.3f} s: start to data {t_data - t0:.3f}, data and "
        f"weights {t_warm - t_data:.3f}, warm-up sweep {time.perf_counter() - t_warm:.3f} "
        f"(of it compile or cache load "
        f"{'not counted' if compile_s is None else f'{compile_s:.3f}'})")

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
            kept.append((seeds, program.kept(recs)))
            k += 1
            seeds = sweep_seeds(args.seed, k, traffic["seeds"])
        if window_s >= args.seconds:
            break
    n_sweeps = k - 1
    if args.trace:
        jax.profiler.stop_trace()
    compiles1 = program.counters()[1]
    window_compiles = None if compiles0 is None else compiles1 - compiles0
    rate = program.cell_rounds_per_sweep(traffic) * n_sweeps / window_s
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
    del prog, recs
    program.release()
    gc.collect()

    t_ref = time.perf_counter()
    readings = program.check(config, traffic, data, params0, kept)
    numbers = check.merge(readings)
    limits = config["limits"]
    correct, rows = check.verdict(numbers, limits)
    failed = sum(not check.verdict(r, limits)[0] for r in readings)
    log(f"reference over {len(kept)} sweeps in {time.perf_counter() - t_ref:.3f} s; "
        + ", ".join(f"{k} {v}" for k, v in numbers.items() if k.startswith("cells_")))

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
            program=program,
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
