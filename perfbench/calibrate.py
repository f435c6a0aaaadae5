"""Readings the limits of ``perfbench/check.py`` are set from.

    python3 -m perfbench.calibrate --workload <name> --seeds 12 --controls 3 \
        --out chiprun_out/perfbench/calibrate-<name>.json

Runs on the cell's chips at the cell's own size, in one process:

* the program: one sweep for each of ``--seeds`` run seeds, through the
  same calls a run makes, compared with the reference (the lower
  readings);
* the control: the reference one precision below the configuration's,
  bfloat16, put in the program's place, on the first ``--controls`` seeds;
* the faults a training cell can have, planted in the reference put in the
  program's place: a round that leaves the weights unchanged, and half of
  each device's mini-batch left out (the mean taken over the rest).

Each reading is given at several ``tie_margin`` values, with the cells a
margin leaves out, and for the program the worst cells with their
margins, to show which gaps come from draws decided by rounding.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

from perfbench import check
from perfbench.programs.lattice import CHECK_ROUNDS, Program, cell_grid, first_rounds
from perfbench.run import ROOT, enable_compile_cache, load_cell, seed32, sweep_seeds

MARGINS = (0.0, 1e-5, 1e-4, 1e-3, 1e-2)


class ReferenceProgram:
    """A lattice :class:`perfbench.programs.lattice.Program` whose sweeps are the reference's
    first rounds: with ``dtype=bfloat16`` the control, with ``fault`` a
    planted fault."""

    def __init__(self, config, traffic, data, params0, **ref_kw):
        from perfbench.data import param_shapes
        from perfbench.reference import Reference, flatten, layout

        self.traffic = traffic
        self.ref = Reference(config, *data, n_rounds=CHECK_ROUNDS, **ref_kw)
        self.w0 = flatten(params0, layout(param_shapes(config)))

    def sweep(self, seeds):
        from perfbench.reference import POLICIES

        grid = cell_grid(self.traffic, seeds)
        rec = self.ref.run(
            self.w0, [g[3] for g in grid], [g[1] for g in grid],
            [g[2] for g in grid], [POLICIES.index(g[0]) for g in grid],
        )
        # the records in the shape ``first_rounds`` reads
        return SimpleNamespace(
            **{f: rec[f] for f in ("grad_norm", "e_com", "e_var", "n_scheduled")},
            eval=SimpleNamespace(loss=rec["eval0_loss"][:, None],
                                 acc=rec["eval0_acc"][:, None]),
        )


def cell_gaps(got, want) -> np.ndarray:
    """Per-cell worst gap over the gap fields and rounds."""
    worst = np.zeros(np.asarray(want["margin"]).shape)
    for f in check.GAP_FIELDS + check.EVAL_FIELDS:
        p = np.asarray(got[f], np.float64).reshape(worst.shape[0], -1)
        r = np.asarray(want[f], np.float64).reshape(worst.shape[0], -1)
        den = np.maximum(np.abs(r), np.median(np.abs(r)))
        g = np.abs(p - r) / np.where(den > 0, den, 1.0)
        worst = np.maximum(worst, np.max(g, axis=1))
    return worst


def readings(got, want) -> dict:
    return {str(m): check.compare(got, want, m) for m in MARGINS}


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9_000_000_001)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    _, cell, config, traffic = load_cell(root, args.workload)
    if config.get("program", "lattice") != "lattice":
        print(f"calibrate: {args.workload} runs program {config['program']!r}; "
              "this tool calibrates only the lattice's", file=sys.stderr)
        return 2
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    enable_compile_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    from perfbench import data as bdata
    from perfbench.reference import POLICIES, Reference, flatten, layout

    data = bdata.make_dataset(config)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    params = {s: bdata.init_params(config, jax.random.PRNGKey(seed32(s, 1 << 40)))
              for s in seeds}
    prog = Program(config, traffic, data, params[seeds[0]])
    got, sweep_s = {}, []
    for s in seeds:
        prog.params0 = params[s]
        t = time.perf_counter()
        got[s] = first_rounds(prog.sweep(sweep_seeds(s, 0, traffic["seeds"])))
        sweep_s.append(time.perf_counter() - t)
    peak = max((st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0))
               for st in (d.memory_stats() or {} for d in devices[: cell["chips"]]))
    del prog
    from repro.sim import reset_engine_cache

    reset_engine_cache()
    gc.collect()

    lay = layout(bdata.param_shapes(config))
    ref = Reference(config, *data, n_rounds=CHECK_ROUNDS)
    others = {
        "control_bf16": Reference(config, *data, n_rounds=CHECK_ROUNDS, dtype=jnp.bfloat16),
        "fault_frozen": Reference(config, *data, n_rounds=CHECK_ROUNDS, fault="frozen"),
        "fault_half_batch": Reference(config, *data, n_rounds=CHECK_ROUNDS, fault="half_batch"),
    }
    out = {"workload": args.workload, "seeds": seeds, "sweep_s": sweep_s,
           "peak_bytes": int(peak), "program": {}, "worst_cells": {},
           **{k: {} for k in others}}
    for i, s in enumerate(seeds):
        grid = cell_grid(traffic, sweep_seeds(s, 0, traffic["seeds"]))
        cols = ([g[3] for g in grid], [g[1] for g in grid], [g[2] for g in grid],
                [POLICIES.index(g[0]) for g in grid])
        w0 = flatten(params[s], lay)
        t = time.perf_counter()
        want = ref.run(w0, *cols)
        ref_s = time.perf_counter() - t
        out["program"][s] = readings(got[s], want)
        out["program"][s]["reference_s"] = ref_s
        gaps = cell_gaps(got[s], want)
        worst = np.argsort(-gaps)[:8]
        out["worst_cells"][s] = [
            {"cell": int(c), "policy": grid[c][0], "noise": grid[c][1],
             "gap": float(gaps[c]), "margin": float(want["margin"][c])}
            for c in worst
        ]
        if i < args.controls:
            for name, r in others.items():
                rec = r.run(w0, *cols)
                out[name][s] = readings(
                    {k: rec[k] for k in got[s]}, want
                )
        print(json.dumps({"seed": s, "program": out["program"][s][str(MARGINS[0])],
                          "ref_s": ref_s}), file=sys.stderr, flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(json.dumps({"ok": True, "out": args.out, "device": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
