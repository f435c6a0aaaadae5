"""Compile a lattice cell's program for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 -m perfbench.aot --workload <name>

Prints XLA's per-device memory analysis of the one program the cell's
sweeps run (a compiler count, not a measurement) and whether the fused
kernel is in it. Code that asks for the backend still sees the CPU here,
so the kernel's dispatch is told it is on a TPU for the trace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    from perfbench.programs.lattice import Program, cell_grid
    from perfbench.run import ROOT, load_cell, sweep_seeds

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro.kernels.aircomp.ops as ops
    from repro.sim.engine import FUSED_POLICY, cached_engine

    from perfbench import data as bdata

    _, cell, config, traffic = load_cell(ROOT, args.workload)
    if config.get("program", "lattice") != "lattice":
        print(f"aot: {args.workload} runs program {config['program']!r}; this "
              "tool compiles only the lattice's", file=sys.stderr)
        return 2
    ops._on_tpu = lambda: True
    chips = cell["chips"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    data = bdata.make_dataset(config)
    params0 = bdata.init_params(config, jax.random.PRNGKey(0))
    prog = Program(config, traffic, data, params0)
    import dataclasses

    cfg = dataclasses.replace(prog.cfg, policy=FUSED_POLICY)
    mesh = None
    if chips > 1:
        devs = np.asarray(topo.devices[:chips]).reshape(traffic["mesh"])
        mesh = Mesh(devs, ("cells", "model"))
    eng = cached_engine(prog.loss_fn, prog.data, cfg, eval_fn=prog.eval_fn, mesh=mesh)
    grid = cell_grid(traffic, sweep_seeds(0, 0, traffic["seeds"]))
    n = len(grid)
    if mesh is None:
        cell_sh = rep = SingleDeviceSharding(topo.devices[0])
    else:
        cell_sh = NamedSharding(mesh, PartitionSpec("cells"))
        rep = NamedSharding(mesh, PartitionSpec())

    def sds(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    rounds = traffic["rounds"]
    args_ = (
        jax.tree.map(lambda x: sds(x.shape, x.dtype, rep), params0),
        sds((rounds,), jnp.int32, rep), sds((rounds,), jnp.bool_, rep),
        sds((n,), jnp.float32, cell_sh), sds((n,), jnp.float32, cell_sh),
        sds((n,), jnp.int32, cell_sh), sds((n,), jnp.int32, cell_sh),
    )
    t = time.perf_counter()
    compiled = eng._fused_lattice_jit.lower(*args_).compile()
    mem = compiled.memory_analysis()
    print(json.dumps({
        "workload": args.workload, "chips": chips, "cells": n,
        "compile_s": time.perf_counter() - t,
        "kernel": "tpu_custom_call" in compiled.as_text(),
        "per_device_bytes": {
            k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
            )
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
