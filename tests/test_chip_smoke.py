"""``chip_smoke.py``: it refuses to run without a TPU, and its phases run
through on the CPU at tiny sizes with interpret-mode kernels, the fused
backend agreeing with the jnp backend within the script's own tolerance."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, SCRIPT], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_phases_run_and_agree_at_tiny_sizes(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    smoke = _load()
    lat = smoke.lattice_phase(
        kind="logreg", n_devices=6, n_scheduled=3, n_train=120, n_test=60,
        seeds=(0,), n_rounds=2,
    )
    assert lat["cells"] == 5
    assert lat["first_round_max_rel"] <= smoke.FIRST_ROUND_RTOL
    assert lat["loss_max_rel"] <= smoke.RTOL
    assert lat["e_com_max_rel"] <= smoke.RTOL
    assert lat["acc_max_abs"] <= smoke.ACC_TOL
    assert not lat["custom_call"]  # interpret mode compiles no TPU kernel

    pofl = smoke.pofl_phase(n_devices=6, n_scheduled=3, n_train=120, n_test=60,
                            n_rounds=4)
    assert pofl["loss_last"] < pofl["loss_first"]

    tr = smoke.trainer_phase(batch=2, seq=16, n_rounds=2, reduced=True)
    assert len(tr["losses"]) == 2 and np.all(np.isfinite(tr["losses"]))
