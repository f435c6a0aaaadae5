"""Real-model federated tasks on the lattice — and the golden recipe.

Runs the model-task battery configuration from ``tests/test_model_tasks.py``
verbatim and prints the full-precision accuracy/loss/n_correct curves: this
script IS the regeneration recipe for the ``GOLDEN_LOGREG`` / ``GOLDEN_CNN``
tables (rerun after an INTENTIONAL semantics change, paste the output).

The task factory (``repro.sim.make_model_task``) bundles a real pytree model
(784-dim logistic regression, or the 4-conv CNN with D = 258 634 raveled
params), Dirichlet-sized PADDED heterogeneous shards, and a pad-masked
:class:`~repro.sim.tasks.TaskEval` whose structured ``EvalRecord`` curves the
lattice stacks onto ``LatticeRecords.eval`` — the whole multi-policy sweep is
still ONE trace / ONE compile:

    PYTHONPATH=src python examples/model_tasks.py              # logreg (~10 s)
    PYTHONPATH=src python examples/model_tasks.py --task cnn   # CNN (~1-2 min)

CNN note: XLA CPU lowers in-scan conv grads to naive loops (~0.5 s per train
sample per round on one core), so the CNN cells are deliberately tiny — the
point is the paper-scale pytree plumbing, not throughput.
"""
import argparse
import time

import numpy as np

from repro.core.pofl import POFLConfig
from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
from repro.sim import (
    FUSED_POLICY,
    LatticeSpec,
    cached_engine,
    make_model_task,
    run_lattice,
)

# the EXACT battery configurations tests/test_model_tasks.py pins
BATTERY = {
    "logreg": dict(
        task_kw=dict(kind="logreg", n_devices=8, partition="dirichlet_sized",
                     n_train=640, n_test=256, seed=0),
        cfg=dict(n_devices=8, n_scheduled=3, batch_size=8, lr0=0.1),
        spec=dict(n_rounds=6, eval_every=2),
    ),
    "cnn": dict(
        task_kw=dict(kind="cnn", n_devices=4, partition="dirichlet_sized",
                     n_train=64, n_test=24, seed=0, channel_bias=1.0),
        cfg=dict(n_devices=4, n_scheduled=2, batch_size=4, lr0=0.1),
        spec=dict(n_rounds=3, eval_every=2),
    ),
}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--task", default="logreg", choices=sorted(BATTERY),
        help="which battery to run (and which golden table to print)",
    )
    args = parser.parse_args(argv)
    enable_compile_cache(CHECKOUT_CACHE_DIR)
    b = BATTERY[args.task]

    task = make_model_task(**b["task_kw"])
    spec = LatticeSpec(policies=("pofl", "channel"), noise_powers=(1e-11,),
                       alphas=(0.1,), seeds=(0,), **b["spec"])
    t0 = time.time()
    recs = run_lattice(
        task.loss_fn, task.data, task.params0, spec,
        base_cfg=POFLConfig(**b["cfg"]), eval_fn=task.eval,
    )
    dt = time.time() - t0
    eng = cached_engine(
        task.loss_fn, task.data,
        POFLConfig(policy=FUSED_POLICY, **b["cfg"]), eval_fn=task.eval,
    )
    print(f"{args.task}: D={task.dim} shards={np.asarray(task.data.n_samples)}"
          f" — {spec.n_cells} cells × {spec.n_rounds} rounds in {dt:.1f}s,"
          f" traces={eng.n_lattice_traces} compiles={eng.n_compiles}")
    print(f"eval rounds: {recs.eval_rounds.tolist()}")
    print(f'GOLDEN_{args.task.upper()} = {{')
    for pi, pol in enumerate(spec.policies):
        print(f'    "{pol}": {{')
        for f in ("acc", "loss", "n_correct"):
            curve = np.asarray(getattr(recs.eval, f)[0, pi, 0, 0, 0])
            print(f'        "{f}": {[float(v) for v in curve]},')
        print("    },")
    print("}")


if __name__ == "__main__":
    main()
