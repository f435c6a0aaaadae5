"""Scenario-lattice quickstart: a whole paper-style sweep in one program.

Runs (3 policies × 2 noise powers × 4 trials) = 24 cells of PO-FL training
through ``repro.sim`` — ONE policy-fused vmapped+scanned compile for the
whole sweep (the policy axis is traced), metrics streamed out once — under
temporally-correlated Gauss–Markov fading with random device dropout
(scenarios the per-round ``run_pofl`` loop cannot express). That one
compile persists across runs in ``$JAX_COMPILATION_CACHE_DIR``, else in the
checkout's ``.jax_cache``. ``--mesh N`` shards the 8-cell-per-policy axis over N devices
(results are identical — only placement changes):

    PYTHONPATH=src python examples/sim_lattice.py [--backend pallas_fused]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sim_lattice.py --mesh 8

``--algorithms a,b`` (``repro.core.local_update.ALGORITHMS`` names) adds a
traced local-update algorithm axis — still the same single compile — and
``--local-steps K`` runs K local SGD steps per device per round:

    PYTHONPATH=src python examples/sim_lattice.py \
        --algorithms fedavg,fedprox --local-steps 3

``--distributed`` initializes ``jax.distributed`` from the ``REPRO_DIST_*``
env contract and shards the cell axis over the GLOBAL (process-spanning)
device list — run it under the local launcher (2 hosts × 4 fake CPU devices
each; every host prints the same gathered records):

    PYTHONPATH=src python -m repro.launch.distributed \
        --procs 2 --devices-per-proc 4 -- \
        python examples/sim_lattice.py --distributed
"""
import argparse

import jax
import numpy as np

from repro.core.pofl import BACKENDS, POFLConfig
from repro.data.synthetic import make_classification_dataset
from repro.models import small
from repro.sim import (
    LatticeSpec,
    enable_compile_cache,
    initialize_distributed,
    lattice_compile_stats,
    make_cell_mesh,
    make_global_cell_mesh,
    make_partition,
    run_lattice,
)
from repro.sim.compile_cache import CHECKOUT_CACHE_DIR


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--backend", default="jnp", choices=BACKENDS,
        help="aggregation backend (pallas_fused = fused kernel on TPU, "
        "its jnp oracle on CPU)",
    )
    parser.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="shard the cell axis over the first N local devices "
        "(0 = unsharded; on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N first)",
    )
    parser.add_argument(
        "--distributed", action="store_true",
        help="initialize jax.distributed from the REPRO_DIST_* env contract "
        "and shard the cell axis over ALL global devices (see "
        "repro.launch.distributed)",
    )
    parser.add_argument(
        "--rounds", type=int, default=30, metavar="T",
        help="rounds per cell (shrink for smoke runs)",
    )
    parser.add_argument(
        "--algorithms", type=str, default="fedavg", metavar="A[,B...]",
        help="comma-separated local-update algorithms "
        "(repro.core.local_update.ALGORITHMS names); >1 name sweeps the "
        "traced algorithm axis inside the same single compile",
    )
    parser.add_argument(
        "--local-steps", type=int, default=1, metavar="K",
        help="local SGD steps per device per round (1 = the classic "
        "single-gradient round)",
    )
    args = parser.parse_args(argv)
    algorithms = tuple(s.strip() for s in args.algorithms.split(","))

    cache_dir = enable_compile_cache(CHECKOUT_CACHE_DIR)

    if args.distributed:
        # must precede the first device query; a missing env contract just
        # degrades to a single-process run over the local devices
        initialize_distributed()
        mesh = make_global_cell_mesh(args.mesh or None)  # --mesh counts GLOBAL devices here
    else:
        mesh = make_cell_mesh(args.mesh) if args.mesh else None

    key = jax.random.PRNGKey(0)
    k_train, k_test, k_init = jax.random.split(key, 3)
    x_tr, y_tr = make_classification_dataset("mnist_like", 3000, k_train)
    x_te, y_te = make_classification_dataset("mnist_like", 1000, k_test)
    # Dirichlet(0.3) label skew — the sim subsystem's third partition preset
    data = make_partition("dirichlet", x_tr, y_tr, n_devices=20, beta=0.3)

    params0 = small.init_logreg(k_init)
    eval_fn = small.make_eval_fn(small.logreg_logits, small.logreg_loss, x_te, y_te)

    spec = LatticeSpec(
        policies=("pofl", "importance", "channel"),
        noise_powers=(1e-11, 1e-9),
        seeds=(0, 1000, 2000, 3000),
        n_rounds=args.rounds,
        eval_every=10,
        algorithms=algorithms,
    )
    records = run_lattice(
        small.logreg_loss, data, params0, spec,
        base_cfg=POFLConfig(n_devices=20, n_scheduled=8, backend=args.backend,
                            local_steps=args.local_steps),
        eval_fn=eval_fn,
        scenario="dropout",
        scenario_params={"base": "gauss_markov", "corr": 0.9, "p_drop": 0.1},
        mesh=mesh,
    )

    if mesh is None:
        shard_note = ""
    else:
        n_dev = int(np.asarray(mesh.devices).size)
        shard_note = f", cells sharded over {n_dev} devices"
        if args.distributed:
            shard_note += f" ({jax.process_count()} hosts)"
    cs = lattice_compile_stats()
    cache_note = f", compile cache {cache_dir}" if cache_dir else ""
    print(f"lattice: {spec.n_cells} cells × {spec.n_rounds} rounds "
          f"(eval rounds {records.eval_rounds.tolist()}){shard_note} — "
          f"{cs['n_compiles']} compile(s), {cs['compile_seconds']:.1f}s"
          f"{cache_note}")
    for policy in spec.policies:
        for np_ in spec.noise_powers:
            acc = records.cell(policy=policy, noise_power=np_)["acc"]
            best = np.mean(np.max(acc, axis=-1))  # mean-over-trials best acc
            print(f"  {policy:>11s} @ σ_z²={np_:.0e}:  best_acc={best:.3f}")


if __name__ == "__main__":
    main()
