"""Chip smoke test: drive the PO-FL system's two entry points once on a TPU.

    python chip_smoke.py             # one chip: lattice, run_pofl, trainer
    python chip_smoke.py --chips 4   # the (cells x model) mesh lattice only

Everything runs in this one process and it starts no JAX children: a chip
belongs to one process at a time. With no TPU it exits non-zero at once —
there is no CPU fallback and it never selects interpret mode. Any failed
phase exits non-zero. Earlier lines print observations (wall and compile
seconds, peak device bytes, the agreement reached); they are not benchmark
numbers. The last line of stdout is one JSON object naming the device.

Phases:

  lattice   ``run_lattice`` over the CNN task at the paper's width
            (N = 30 devices, D = 258,634): 5 policies x 2 seeds x 3 rounds,
            once with ``backend="pallas_fused"`` and once with ``"jnp"`` on
            the physical Eq. 5-8 chain (the same semantics). The fused
            program must contain the compiled kernel (``tpu_custom_call``),
            every record must be finite, and the two backends' loss,
            accuracy and e_com curves must agree within ``RTOL``/``ATOL``
            and ``ACC_TOL`` (the first round's aggregate within
            ``FIRST_ROUND_RTOL``).
  run_pofl  one ``run_pofl`` call on the logreg task: the engine's donated
            scan carry.
  trainer   ``POFLTrainer`` on qwen2-0.5b at its published widths (24
            layers, d_model 896, vocab 151,936), 3 rounds of batch 8 x
            sequence 512, finite losses. That size was picked by compiling
            the train and stats steps for a described v5e chip: the train
            step needs about 9 GiB and the stats step about 6.5 GiB of the
            chip's 16 GB, where batch 16 x sequence 1024 does not fit.
  mesh      (``--chips 4`` only) ``run_lattice`` with the CNN task and
            ``pallas_fused`` on ``make_cell_model_mesh(2, 2)``, against the
            same spec unsharded on one device in this process; the records
            must span 4 devices and agree within the same tolerance.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Agreement between two runs of one lattice spec (fused kernel vs the jnp
# physical chain, or sharded vs unsharded). The first round aggregates
# identical gradients, so its aggregate norm and communication error differ
# only by float32 summation order. Later rounds start from parameters that
# differ by that much, and on a TPU the CNN's convolutions run at the
# default (bf16-pass) matmul precision, which lifts those differences to
# about 1e-3 by round 3 (e_com 2.1e-3 relative on a TPU v5e at the default
# sizes). Accuracy is a count over test rows, so it moves by whole rows.
FIRST_ROUND_RTOL = 1e-4
RTOL = 1e-2
ATOL = 1e-5
ACC_TOL = 0.01  # fraction of test rows

TRAINER_BATCH, TRAINER_SEQ = 8, 512


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check_finite(recs, what: str) -> None:
    named = {
        f: getattr(recs, f)
        for f in ("e_com", "e_var", "grad_norm", "n_scheduled", "loss", "acc")
    }
    if recs.eval is not None:
        named.update((f"eval.{k}", v) for k, v in recs.eval._asdict().items())
    for name, arr in named.items():
        if not np.all(np.isfinite(np.asarray(arr))):
            raise AssertionError(f"{what}: non-finite {name} records")


def _max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / (np.abs(b) + ATOL), initial=0.0))


def _compare(got, want, what: str) -> dict:
    """Assert two LatticeRecords agree within the stated tolerance; return
    the largest deviations seen."""
    for f in ("grad_norm", "e_com"):
        np.testing.assert_allclose(
            getattr(got, f)[..., 0], getattr(want, f)[..., 0],
            rtol=FIRST_ROUND_RTOL, atol=ATOL,
            err_msg=f"{what}: first-round {f} disagrees",
        )
    for f in ("loss", "e_com"):
        np.testing.assert_allclose(
            getattr(got, f), getattr(want, f), rtol=RTOL, atol=ATOL,
            err_msg=f"{what}: {f} curves disagree",
        )
    np.testing.assert_allclose(
        got.acc, want.acc, rtol=0, atol=ACC_TOL,
        err_msg=f"{what}: accuracy curves disagree",
    )
    np.testing.assert_array_equal(
        got.n_scheduled, want.n_scheduled,
        err_msg=f"{what}: scheduled-set sizes disagree",
    )
    return {
        "first_round_max_rel": max(
            _max_rel(getattr(got, f)[..., 0], getattr(want, f)[..., 0])
            for f in ("grad_norm", "e_com")
        ),
        "loss_max_rel": _max_rel(got.loss, want.loss),
        "e_com_max_rel": _max_rel(got.e_com, want.e_com),
        "acc_max_abs": float(np.max(np.abs(got.acc - want.acc), initial=0.0)),
    }


def _lattice(task, spec, cfg, mesh=None):
    """One cold run_lattice call on a fresh engine cache; returns (records,
    its executable, {"seconds", "compile_seconds"})."""
    from repro.sim import (
        latest_lattice_executable,
        lattice_compile_stats,
        reset_engine_cache,
        run_lattice,
    )

    reset_engine_cache()
    t0 = time.perf_counter()
    recs = run_lattice(
        task.loss_fn, task.data, task.params0, spec,
        base_cfg=cfg, eval_fn=task.eval, mesh=mesh,
    )
    times = {
        "seconds": time.perf_counter() - t0,
        "compile_seconds": lattice_compile_stats()["compile_seconds"],
    }
    return recs, latest_lattice_executable(), times


def _lattice_setup(kind, n_devices, n_scheduled, n_train, n_test, seeds,
                   n_rounds, batch_size):
    from repro.core.pofl import POFLConfig
    from repro.core.scheduling import POLICIES
    from repro.sim import LatticeSpec, make_model_task

    task = make_model_task(
        kind, n_devices=n_devices, partition="iid", n_train=n_train,
        n_test=n_test,
    )
    spec = LatticeSpec(
        policies=POLICIES, seeds=tuple(seeds), n_rounds=n_rounds, eval_every=1,
    )
    cfg = POFLConfig(
        n_devices=n_devices, n_scheduled=n_scheduled, batch_size=batch_size,
        simulate_physical=True,
    )
    return task, spec, cfg


def lattice_phase(kind="cnn", n_devices=30, n_scheduled=10, n_train=3000,
                  n_test=500, seeds=(0, 1), n_rounds=3, batch_size=10) -> dict:
    """The lattice under both backends; raises unless the records are finite
    and agree. Returns observations, incl. whether the fused program holds a
    compiled kernel (``custom_call``) — the caller decides what it needs."""
    task, spec, cfg = _lattice_setup(
        kind, n_devices, n_scheduled, n_train, n_test, seeds, n_rounds,
        batch_size,
    )
    fused, fused_exe, fused_t = _lattice(
        task, spec, dataclasses.replace(cfg, backend="pallas_fused")
    )
    ref, _, ref_t = _lattice(task, spec, dataclasses.replace(cfg, backend="jnp"))
    _check_finite(fused, "pallas_fused lattice")
    _check_finite(ref, "jnp lattice")
    return {
        "task": kind, "dim": task.dim, "n_devices": n_devices,
        "cells": spec.n_cells, "rounds": n_rounds,
        "custom_call": "tpu_custom_call" in fused_exe.as_text(),
        "pallas_fused": fused_t, "jnp": ref_t,
        **_compare(fused, ref, "pallas_fused vs jnp"),
    }


def pofl_phase(n_devices=30, n_scheduled=10, n_train=3000, n_test=500,
               n_rounds=10) -> dict:
    """One run_pofl call on the logreg task (the donated scan carry)."""
    from repro.core.pofl import POFLConfig, run_pofl
    from repro.sim import make_model_task

    task = make_model_task(
        "logreg", n_devices=n_devices, partition="iid", n_train=n_train,
        n_test=n_test,
    )
    cfg = POFLConfig(
        n_devices=n_devices, n_scheduled=n_scheduled, backend="pallas_fused",
    )
    t0 = time.perf_counter()
    params, hist = run_pofl(
        task.loss_fn, task.params0, task.data, cfg, n_rounds,
        eval_fn=task.eval, eval_every=5,
    )
    seconds = time.perf_counter() - t0
    for name in ("loss", "e_com", "e_var", "test_acc"):
        if not np.all(np.isfinite(np.asarray(getattr(hist, name), np.float64))):
            raise AssertionError(f"run_pofl: non-finite {name}")
    if not np.all(np.isfinite(np.asarray(task.ravel(params)))):
        raise AssertionError("run_pofl: non-finite params")
    if not hist.loss[-1] < hist.loss[0]:
        raise AssertionError(f"run_pofl: loss did not fall {hist.loss}")
    return {
        "rounds": n_rounds, "seconds": seconds,
        "loss_first": float(hist.loss[0]), "loss_last": float(hist.loss[-1]),
        "acc_last": float(hist.test_acc[-1]),
    }


def trainer_phase(arch="qwen2-0.5b", batch=TRAINER_BATCH, seq=TRAINER_SEQ,
                  n_rounds=3, reduced=False) -> dict:
    """POFLTrainer for ``n_rounds`` on a one-device mesh; raises unless every
    loss is finite. ``reduced`` swaps in the family's smoke widths."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.data.synthetic import make_token_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import POFLTrainer, TrainerConfig, run_training
    from repro.models.config import InputShape

    cfg = configs.reduced_config(arch) if reduced else configs.base_config(arch)
    shape = InputShape("smoke", seq_len=seq, global_batch=batch, kind="train")
    mesh = make_host_mesh(model=1)
    t0 = time.perf_counter()
    trainer = POFLTrainer(cfg, shape, mesh, TrainerConfig())
    tokens = make_token_dataset(
        batch * 4, seq, cfg.vocab_size, jax.random.PRNGKey(0)
    )

    def batch_fn(t):
        return {"tokens": tokens[jnp.arange(batch) + (t % 4) * batch]}

    _, _, losses = run_training(trainer, batch_fn, n_rounds)
    seconds = time.perf_counter() - t0
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"trainer: non-finite losses {losses}")
    return {
        "arch": arch, "params": cfg.param_count(), "layers": cfg.n_layers,
        "d_model": cfg.d_model, "vocab": cfg.vocab_size, "batch": batch,
        "seq": seq, "rounds": n_rounds, "seconds": seconds,
        "losses": [float(x) for x in losses],
    }


def mesh_phase(cells=2, model=2, kind="cnn", n_devices=30, n_scheduled=10,
               n_train=3000, n_test=500, seeds=(0, 1), n_rounds=3,
               batch_size=10) -> dict:
    """The fused lattice on a (cells x model) mesh against the same spec
    unsharded on one device; raises unless the sharded records came off all
    ``cells * model`` devices and agree."""
    import jax

    from repro.sim import make_cell_model_mesh

    task, spec, cfg = _lattice_setup(
        kind, n_devices, n_scheduled, n_train, n_test, seeds, n_rounds,
        batch_size,
    )
    cfg = dataclasses.replace(cfg, backend="pallas_fused")
    mesh = make_cell_model_mesh(cells, model)
    sharded, exe, sharded_t = _lattice(task, spec, cfg, mesh=mesh)
    spans = {
        d for s in jax.tree.leaves(exe.output_shardings) for d in s.device_set
    }
    if len(spans) != cells * model:
        raise AssertionError(
            f"sharded lattice outputs span {len(spans)} devices, "
            f"expected {cells * model}"
        )
    single, _, single_t = _lattice(task, spec, cfg)
    _check_finite(sharded, "sharded lattice")
    _check_finite(single, "unsharded lattice")
    return {
        "mesh": f"{cells}x{model}", "devices_spanned": len(spans),
        "custom_call": "tpu_custom_call" in exe.as_text(),
        "cells": spec.n_cells, "dim": task.dim,
        "sharded": sharded_t, "unsharded": single_t,
        **_compare(sharded, single, f"{cells}x{model} mesh vs unsharded"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the (cells x model) mesh phase and its comparison",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX's first device is "
            f"{devices[0].platform!r}); this smoke test runs only on a chip",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
            f"devices, found {len(devices)}", file=sys.stderr,
        )
        return 2

    from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    cache = enable_compile_cache(CHECKOUT_CACHE_DIR)
    kind = devices[0].device_kind
    _log(f"device {kind} x{len(devices)}; compile cache {cache}")

    if args.chips == 4:
        phases = [("mesh", mesh_phase)]
    else:
        phases = [
            ("lattice", lattice_phase),
            ("run_pofl", pofl_phase),
            ("trainer", trainer_phase),
        ]
    from repro.sim import reset_engine_cache

    for name, phase in phases:
        t0 = time.perf_counter()
        obs = phase()
        if "custom_call" in obs and not obs["custom_call"]:
            raise AssertionError(f"{name}: no tpu_custom_call in the fused lattice")
        obs["wall_seconds"] = time.perf_counter() - t0
        # process-lifetime peak so far, as the backend reports it
        obs["peak_bytes_in_use"] = (devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use"
        )
        _log(f"{name}: {json.dumps(obs)}")
        reset_engine_cache()  # release the phase's engines and executables

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform, "kind": kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
