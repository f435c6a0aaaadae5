"""The PO-FL scenario lattice (``repro.sim.run_lattice``) as a benchmark
program: what a configuration without a ``program`` key runs.

A sweep is one ``run_lattice`` call over the traffic's grid of policies x
noise powers x alphas x run seeds, ``rounds`` rounds each, with the
configuration's task (``logreg`` or ``cnn``) on the physical Eq. 5-8
aggregation chain and the fused kernel. The check follows the first
:data:`CHECK_ROUNDS` rounds of every cell of every sweep with the plain
reference (``perfbench/reference.py``) and compares them in
``perfbench/check.py``.

The dataset comes from the configuration's fixed ``data_seed``, and the
initial weights and every lattice seed (channels, mini-batches,
scheduling draws, receiver noise) from ``--seed``: the program bakes its
dataset into the compiled lattice as a constant, so a dataset drawn from
``--seed`` would recompile every run. Sweep ``k``'s lattice seeds are
values the program vmaps, so nothing compiles in the window.

The interface every program module supplies is described in
``perfbench/run.py``.
"""
from __future__ import annotations

import itertools

import numpy as np

from perfbench import data as bdata

CHECK_ROUNDS = 3  # rounds of each sweep the reference follows


def cell_grid(traffic: dict, seeds: tuple) -> list:
    """(policy, noise power, alpha, seed) of each cell, in the lattice's
    flat order: policy-major, then noise, alpha, seed."""
    return list(itertools.product(
        traffic["policies"], traffic["noise_powers"], traffic["alphas"], seeds
    ))


def n_cells(traffic: dict) -> int:
    """Cells of one sweep."""
    return len(cell_grid(traffic, tuple(range(traffic["seeds"]))))


def cell_rounds_per_sweep(traffic: dict) -> int:
    """Cell-rounds one sweep completes."""
    return n_cells(traffic) * traffic["rounds"]


def make_data(config: dict):
    """The configuration's dataset, from its fixed ``data_seed``."""
    return bdata.make_dataset(config)


def init_params(config: dict, key):
    """The initial weights, from the run's key, on the device."""
    return bdata.init_params(config, key)


class Program:
    """The system under test: the lattice of the cell, as users drive it."""

    def __init__(self, config: dict, traffic: dict, data, params0):
        import jax.numpy as jnp

        from repro.core.pofl import DeviceData, POFLConfig
        from repro.models import small
        from repro.sim import LatticeSpec, run_lattice
        from repro.sim.tasks import TaskEval

        tasks = {
            "logreg": (small.logreg_loss, small.logreg_logits),
            "cnn": (small.cnn_loss, small.cnn_logits),
        }
        bdata.check_task(config)
        fx, fy, x_te, y_te = data
        self._spec_cls = LatticeSpec
        self._run = run_lattice
        self.loss_fn, logits_fn = tasks[config["task"]]
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


kept = first_rounds


def counters() -> tuple:
    """(seconds spent compiling or loading lattice programs, lattice
    programs compiled), from the program's own counters."""
    from repro.obs.registry import metric_value

    return (float(metric_value("lattice.compile_seconds")),
            int(metric_value("lattice.n_compiles")))


def release() -> None:
    """Drop the program's cached engines and compiled lattices."""
    from repro.sim import reset_engine_cache

    reset_engine_cache()


def check(config: dict, traffic: dict, data, params0, kept_list: list) -> list:
    """One reading of ``perfbench/check.py`` per kept sweep: its first
    rounds against the reference's on the same cells."""
    from perfbench import check as bcheck
    from perfbench.reference import POLICIES, Reference, flatten, layout

    w0_flat = flatten(params0, layout(bdata.param_shapes(config)))
    ref = Reference(config, *data, n_rounds=CHECK_ROUNDS)
    readings = []
    for seeds_k, got in kept_list:
        grid = cell_grid(traffic, seeds_k)
        want = ref.run(
            w0_flat, [g[3] for g in grid], [g[1] for g in grid],
            [g[2] for g in grid], [POLICIES.index(g[0]) for g in grid],
        )
        readings.append(bcheck.compare(got, want, config["tie_margin"]))
    return readings


def forward_flops(config) -> int:
    """Forward FLOPs of one sample (a multiply-add counts 2; convolutions
    and matrix products only)."""
    bdata.check_task(config)
    if config["task"] == "logreg":
        return 2 * int(np.prod(config["input_shape"])) * config["n_classes"]
    h, w, c_prev = config["input_shape"]
    k = config["kernel_size"]
    flops = 0
    for i, c in enumerate(config["conv_channels"]):
        flops += 2 * h * w * k * k * c_prev * c  # SAME padding, stride 1
        c_prev = c
        if i in (1, 2):  # 2x2 max pools after the second and third convs
            h, w = h // 2, w // 2
    flops += 2 * c_prev * config["hidden"] + 2 * config["hidden"] * config["n_classes"]
    return flops


def flops_per_cell_round(config, traffic) -> float:
    """Model FLOPs of one cell-round: 3 x forward FLOPs per sample (forward
    and backward) x N devices x batch x local steps, plus the eval's
    forward FLOPs over the test set on the rounds that evaluate."""
    f = forward_flops(config)
    train = 3 * f * config["n_devices"] * config["batch_size"] * config["local_steps"]
    t = np.arange(traffic["rounds"])
    evals = np.sum((t % traffic["eval_every"] == 0) | (t == traffic["rounds"] - 1))
    return train + f * config["n_test"] * evals / traffic["rounds"]
