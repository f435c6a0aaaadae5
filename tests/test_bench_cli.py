"""benchmarks/run.py CLI topology guards (ISSUE 4 satellite).

A ``--mesh N`` the machine cannot honor used to surface only as a
``CSV,sim_lattice,...,ERROR:...`` line while every other benchmark ran and
no ``BENCH_sim.json`` was written — a silent fallback. The guards now abort
the whole run with exit code 2 before any benchmark executes.
"""
from __future__ import annotations

import os
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402


def _error_code(argv):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(argv)
    return exc.value.code


def test_mesh_exceeding_local_devices_is_hard_error(capsys):
    n_local = len(jax.devices())
    assert _error_code(["--mesh", str(n_local + 1)]) == 2
    err = capsys.readouterr().err
    assert f"needs {n_local + 1} devices but only {n_local}" in err
    assert "xla_force_host_platform_device_count" in err


def test_mesh_2d_syntax_guards(capsys):
    n_local = len(jax.devices())
    # CxM needing more devices than visible: same hard error
    assert _error_code(["--mesh", f"{n_local + 1}x1"]) == 2
    assert "xla_force_host_platform_device_count" in capsys.readouterr().err
    # malformed CxM strings are parser errors, not tracebacks
    assert _error_code(["--mesh", "4x"]) == 2
    assert _error_code(["--mesh", "ax2"]) == 2
    assert _error_code(["--mesh", "4x0"]) == 2
    # model sharding is single-host only
    assert _error_code(["--hosts", "2", "--mesh", "4x2"]) == 2
    assert "single-host" in capsys.readouterr().err


def test_mesh_within_local_devices_passes_guard(monkeypatch):
    """A satisfiable --mesh must NOT trip the guard (the guard may only fire
    on impossible topologies). The benchmarks themselves are stubbed out."""
    monkeypatch.setattr(bench_run, "_run", lambda *a, **k: True)
    bench_run.main(["--mesh", str(len(jax.devices()))])  # no SystemExit


def test_hosts_must_be_positive():
    assert _error_code(["--hosts", "0"]) == 2


def test_multihost_needs_cpu_platform(capsys, monkeypatch):
    """--hosts > 1 spawns CPU workers: without JAX_PLATFORMS=cpu it must
    refuse before anything touches a device."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _error_code(["--hosts", "2"]) == 2
    assert "JAX_PLATFORMS=cpu" in capsys.readouterr().err


def test_failed_phase_exits_nonzero(capsys, monkeypatch):
    """A phase that raises still lets the others print, but the process
    exits non-zero — a run with an ERROR line never reads as a pass."""

    def broken(**kw):
        raise RuntimeError("phase broke")

    monkeypatch.setattr(bench_run, "_bench_sim", broken)
    assert _error_code(["--sim-only"]) == 1
    assert "ERROR:RuntimeError:phase broke" in capsys.readouterr().out


def test_roofline_peaks_only_for_known_devices():
    """The roofline's peaks come from its table; a device it does not list
    (the CPU here) is an error, never a default."""
    from benchmarks.roofline import device_peaks

    assert device_peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks(jax.devices()[0].device_kind)


def test_mesh_must_divide_across_hosts(capsys):
    assert _error_code(["--hosts", "3", "--mesh", "4"]) == 2
    assert "divide evenly" in capsys.readouterr().err


def test_negative_mesh_rejected():
    assert _error_code(["--mesh", "-2"]) == 2


def test_unknown_algorithm_is_hard_error(capsys):
    assert _error_code(["--algorithms", "fedavg,fedsgd"]) == 2
    err = capsys.readouterr().err
    assert "unknown algorithm" in err and "fedsgd" in err


def test_empty_algorithm_name_is_hard_error():
    assert _error_code(["--algorithms", "fedavg,,fedprox"]) == 2
    assert _error_code(["--algorithms", ""]) == 2


def test_local_steps_must_be_positive():
    assert _error_code(["--local-steps", "0"]) == 2


def test_algorithm_axis_is_single_host_only(capsys):
    assert _error_code(["--hosts", "2", "--algorithms", "fedavg,fedprox"]) == 2
    assert "single-host" in capsys.readouterr().err
    assert _error_code(["--hosts", "2", "--local-steps", "3"]) == 2


def test_valid_algorithm_axis_passes_guard(monkeypatch):
    """A well-formed multi-algorithm sweep must NOT trip the guards (the
    benchmarks themselves are stubbed out)."""
    monkeypatch.setattr(bench_run, "_run", lambda *a, **k: True)
    bench_run.main(["--algorithms", "fedavg,fedprox", "--local-steps", "2"])


def test_task_cli_guards(capsys, monkeypatch):
    """--task guards (ISSUE 9 satellite): the CNN's input shape is fixed
    (no --dim) and it is single-host only; unknown names are parser errors;
    a well-formed --task cnn passes the guards."""
    assert _error_code(["--task", "cnn", "--dim", "64"]) == 2
    assert "--dim only applies to the logreg task" in capsys.readouterr().err
    assert _error_code(["--task", "cnn", "--hosts", "2"]) == 2
    assert "single-host" in capsys.readouterr().err
    assert _error_code(["--task", "mlp"]) == 2
    monkeypatch.setattr(bench_run, "_run", lambda *a, **k: True)
    bench_run.main(["--task", "cnn"])  # no SystemExit


def test_bench_task_rejects_dim_for_cifar():
    """Direct (non-CLI) callers get a hard error, not a silent no-op: the
    CNN's input shape is fixed by its architecture, so a ``dim`` override
    with ``kind='cifar'`` must raise instead of being dropped on the floor
    (the CLI guard above only protects ``--task cnn --dim``)."""
    from benchmarks.common import bench_task

    with pytest.raises(ValueError, match="dim override"):
        bench_task(dim=64, kind="cifar")


def test_gate_key_splits_on_task():
    """The perf gate never compares across model tasks: a CNN entry with an
    otherwise-identical topology passes trivially against logreg history
    (and legacy entries WITHOUT the field only match each other)."""
    from benchmarks.report import _gate_key, gate_regression

    base = dict(backend="jnp", mesh_shape=None, mesh_devices=1, n_hosts=1,
                dim=7850, cells=8, n_rounds=10, steady_cells_per_sec=10.0)
    logreg = dict(base, task="logreg")
    cnn = dict(base, task="cnn", dim=258634)
    legacy = dict(base)  # pre-model-task history: no `task` field
    assert _gate_key(logreg) != _gate_key(cnn)
    assert _gate_key(legacy) != _gate_key(logreg)

    ok, msg = gate_regression([logreg, dict(cnn, steady_cells_per_sec=0.1)])
    assert ok and "no prior entry" in msg
    # same task DOES compare (and a 99% drop fails the gate)
    ok, _ = gate_regression(
        [logreg, dict(logreg, steady_cells_per_sec=0.1)]
    )
    assert not ok
