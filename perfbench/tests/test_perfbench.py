"""Tests of the benchmark harness, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

They cover the trace reduction, the FLOP and byte counts, finding cells,
configurations, traffic, programs and metrics by name, the refusal to run
without a TPU, and the check that decides ``correct``: a run of the
program passes it, and runs with the control (the reference one precision
below) or a fault planted under the timed path fail it. A toy second
program, written into a scratch checkout, shows that a configuration the
lattice does not run joins the benchmark as new files alone.
"""
from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import check, trace  # noqa: E402
from perfbench.run import load_cell, load_metric, load_program  # noqa: E402

LATTICE = load_program(ROOT, "lattice")


def _config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


# the 4-conv CNN of the repo's models/small.py on CIFAR-10-shaped images,
# which the yardstick counts for the CNN cells a later change may add
CNN = {"task": "cnn", "input_shape": [32, 32, 3], "n_classes": 10,
       "conv_channels": [32, 64, 128, 128], "kernel_size": 3, "hidden": 128}


# -- trace reduction ---------------------------------------------------------

XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 17000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 15000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%aircomp_fused.3 = f32[8] custom-call(f32[8] %x)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_lattice" } }
  event_metadata { key: 4 value { id: 4 name: "%while.7 = (s32[]) while(s32[] %c)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.sweep" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.between" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } } }
"""


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    ops, spans = trace.events_of(ProfileData.from_text_proto(XSPACE))
    return trace.reduce_events(ops, spans)


def test_trace_window_and_busy_union(reduction):
    # window: first sweep span start (0) to last benchmark span end (20 us)
    assert reduction.window == (0.0, 20000.0)
    d0, d1 = reduction.devices
    assert d0.name == "/device:TPU:0"
    # [1,4) u [2,4) u [6,7) u [12,16) = 3 + 1 + 4 us
    assert d0.busy_ns == 8000.0
    assert d1.busy_ns == 6000.0
    assert reduction.mean(lambda d: d.busy_ns) == 7000.0


def test_trace_time_per_op_and_idle_share(reduction):
    d0 = reduction.devices[0]
    # the while loop's event spans its body's ops: it is not an op of its own
    assert d0.op_ns == {"fusion.1": 7000.0, "aircomp_fused.3": 3000.0}
    assert d0.op_count == {"fusion.1": 2, "aircomp_fused.3": 2}
    idle = load_metric(ROOT, "device_idle_pct").read(_ctx(reduction))
    assert idle == pytest.approx(100 * (1 - 7000 / 20000))


def test_trace_gaps_labelled_by_span(reduction):
    d0 = reduction.devices[0]
    assert d0.gaps == [
        ("perfbench.sweep", 0.0, 1000.0),
        ("perfbench.sweep", 4000.0, 6000.0),
        ("perfbench.between", 7000.0, 12000.0),  # midpoint 9.5 us: between
        ("perfbench.sweep", 16000.0, 20000.0),
    ]
    # last op of sweep 1 ends at 7 us, first op of sweep 2 starts at 12 us
    assert d0.sweep_gaps_ns == [5000.0]
    assert reduction.devices[1].sweep_gaps_ns == []
    assert load_metric(ROOT, "sweep_gap_ms").read(_ctx(reduction)) == 0.005
    bd = trace.breakdown(reduction)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx((7000 + 6000) / 2 / 1e9)]
    assert bd["idle_gaps"][0][0] == "perfbench.sweep"


def test_trace_spread_across_devices(reduction):
    busy = [d.busy_ns for d in reduction.devices]
    assert (max(busy) - min(busy)) / max(busy) == 0.25


def test_kernel_share_of_busy_time(reduction):
    busy = load_metric(ROOT, "aircomp_busy_pct")
    one = trace.Reduction(window=reduction.window, devices=reduction.devices[:1])
    assert busy.read(_ctx(one)) == pytest.approx(100 * 3000 / 8000)


def _ctx(red, **kw):
    base = dict(red=red, config=_config("logreg-mnist-n30"), traffic={},
                chips=len(red.devices) if red else 1, n_cells=1000, rate=1.0,
                compile_s=1.0, window_compiles=0, device_kind="TPU v5 lite",
                program=LATTICE)
    base.update(kw)
    return SimpleNamespace(**base)


def test_readers_return_nothing_without_a_trace():
    for name in ("sweep_gap_ms", "aircomp_roofline_pct", "aircomp_busy_pct",
                 "device_idle_pct"):
        assert load_metric(ROOT, name).read(_ctx(None)) is None


# -- FLOP and byte counts ----------------------------------------------------

def test_forward_flops_by_hand():
    # conv0..3: 32²·9·3·32, 32²·9·32·64, 16²·9·64·128, 8²·9·128·128; fc1, out
    convs = (1024 * 9 * 3 * 32 + 1024 * 9 * 32 * 64 + 256 * 9 * 64 * 128
             + 64 * 9 * 128 * 128)
    cnn = 2 * (convs + 128 * 128 + 128 * 10)
    assert cnn == 96_176_640
    assert LATTICE.forward_flops(CNN) == cnn
    assert LATTICE.forward_flops(_config("logreg-mnist-n30")) == 15_680
    # per cell-round: 3 x fwd x 30 devices x batch 10, plus the eval's
    # 10,000 rows on 6 of 100 rounds (0, 20, 40, 60, 80 and the last)
    traffic = {"rounds": 100, "eval_every": 20}
    assert LATTICE.flops_per_cell_round(_config("logreg-mnist-n30"), traffic) == (
        3 * 15_680 * 300 + 15_680 * 10_000 * 6 / 100)


def test_aircomp_bytes_and_roofline_by_hand():
    roof = load_metric(ROOT, "aircomp_roofline_pct")
    cfg = _config("logreg-mnist-n30")
    # (N, D) gradients + (D,) noise read, (D,) aggregate written, float32
    assert roof.bytes_per_cell_round(cfg) == (30 * 7850 + 7850 + 7850) * 4
    dev = trace.Device("d", busy_ns=1e9, op_ns={"aircomp_fused.12": 2e6},
                       op_count={"aircomp_fused.12": 2}, gaps=[], sweep_gaps_ns=[])
    red = trace.Reduction(window=(0, 1e9), devices=[dev])
    ctx = _ctx(red, n_cells=1000, chips=1)
    want = 100 * 2 * 1000 * 32 * 7850 * 4 / 819e9 / 2e-3
    assert roof.read(ctx) == pytest.approx(want)


# -- finding things by name --------------------------------------------------

def _tiny_root(tmp_path):
    """A checkout whose BENCHMARK.json holds one tiny logreg cell; the
    harness's code is the repository's own."""
    root = tmp_path / "checkout"
    pb = root / "perfbench"
    (pb / "configs").mkdir(parents=True)
    (pb / "traffic").mkdir()
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    os.symlink(os.path.join(ROOT, "perfbench", "metrics"), pb / "metrics")
    os.symlink(os.path.join(ROOT, "perfbench", "programs"), pb / "programs")
    cfg = _config("logreg-mnist-n30")
    cfg.update(name="tiny", n_devices=6, n_scheduled=2, n_train=240, n_test=40,
               batch_size=4)
    (pb / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (pb / "traffic" / "tiny.json").write_text(json.dumps({
        "policies": ["pofl", "importance", "channel", "noisefree", "deterministic"],
        "noise_powers": [1e-11, 1e-9], "alphas": [0.1], "seeds": 2,
        "rounds": 4, "eval_every": 2, "mesh": None,
    }))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                             "file": "perfbench/configs/tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny", "config": "tiny", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _repo_files() -> dict:
    """mtime of every file of the repository's benchmark: BENCHMARK.json
    and all under perfbench/ but bytecode caches."""
    out = {"BENCHMARK.json": os.path.getmtime(os.path.join(ROOT, "BENCHMARK.json"))}
    for dp, dns, fs in os.walk(os.path.join(ROOT, "perfbench")):
        dns[:] = [d for d in dns if d != "__pycache__"]
        for f in fs:
            out[os.path.relpath(os.path.join(dp, f), ROOT)] = os.path.getmtime(
                os.path.join(dp, f))
    return out


@pytest.fixture
def repo_untouched():
    """Fails the test if it added, removed or wrote any benchmark file of
    the repository: what it adds goes to its scratch checkout."""
    before = _repo_files()
    yield
    assert _repo_files() == before


def test_new_cell_and_metric_are_found_by_name(tmp_path, repo_untouched):
    root = _tiny_root(tmp_path)
    os.unlink(root / "perfbench" / "metrics")
    shutil.copytree(os.path.join(ROOT, "perfbench", "metrics"), root / "perfbench" / "metrics")
    (root / "perfbench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    _, cell, config, traffic = load_cell(str(root), "tiny")
    assert (cell["traffic"], config["n_devices"], traffic["rounds"]) == ("tiny", 6, 4)
    assert config.get("program") is None  # the lattice's
    assert load_metric(str(root), "new_metric").read(None) == 42.0


# -- no chip, no result ------------------------------------------------------

def _run_module(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "perfbench", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_without_a_tpu_exits_nonzero_and_prints_no_metrics():
    p = _run_module(ROOT, "--workload", "logreg-fig5-mc50", "--seed",
                    "4294967311", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_module(tmp_path, "--workload", "logreg-fig5-mc50", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- the check that decides `correct` ---------------------------------------

def test_verdict_holds_every_number_to_its_limit():
    limits = {"a_gap": 1e-3, "n_scheduled_mismatch": 0}
    ok, rows = check.verdict({"a_gap": 1e-4, "n_scheduled_mismatch": 0,
                              "cells_compared": 3}, limits)
    assert ok and [r["name"] for r in rows] == ["a_gap", "n_scheduled_mismatch"]
    assert not check.verdict({"a_gap": 2e-3, "n_scheduled_mismatch": 0,
                              "cells_compared": 3}, limits)[0]
    assert not check.verdict({"a_gap": 1e-4, "n_scheduled_mismatch": 1,
                              "cells_compared": 3}, limits)[0]
    assert not check.verdict({"a_gap": 0.0, "n_scheduled_mismatch": 0,
                              "cells_compared": 0}, limits)[0]


def _drive(root, capsys, program_cls=None, workload="tiny"):
    from perfbench.run import main

    rc = main(["--workload", workload, "--seed", "3000000019", "--seconds", "0.2",
               "--trace", "0"], root=str(root), require_tpu=False,
              program_cls=program_cls)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny"))


def test_program_run_is_correct(tiny_root, capsys):
    out = _drive(tiny_root, capsys)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"


def test_control_in_the_programs_place_is_not_correct(tiny_root, capsys):
    import jax.numpy as jnp

    from perfbench.calibrate import ReferenceProgram

    out = _drive(tiny_root, capsys,
                 functools.partial(ReferenceProgram, dtype=jnp.bfloat16))
    assert not out["correct"], out["checks"]


def _frozen(cfg, params, y_hat, t, model_shard=None):
    return params


def _half_batch(loss_fn, data, cfg, params, k_batch):
    from repro.core import local_update as lu

    feats, labels = lu.draw_minibatch(data, cfg, k_batch)
    b = cfg.batch_size // 2
    return lu._device_gradients(loss_fn, params, feats[:, :b], labels[:, :b])


def _altered_answer(real):
    def stage(*args, **kw):
        y_hat, e_com = real(*args, **kw)
        return y_hat.at[0].add(1.0), e_com
    return stage


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch", "altered_answer"])
def test_fault_under_the_timed_path_is_not_correct(tiny_root, capsys, monkeypatch, fault):
    from repro.core import local_update, pofl
    from repro.sim import reset_engine_cache

    reset_engine_cache()
    if fault == "frozen_state":
        monkeypatch.setattr(pofl, "apply_update_stage", _frozen)
    elif fault == "half_batch":
        monkeypatch.setattr(local_update, "local_gradient_stage", _half_batch)
    else:
        monkeypatch.setattr(pofl, "aggregation_stage",
                            _altered_answer(pofl.aggregation_stage))
    out = _drive(tiny_root, capsys)
    reset_engine_cache()
    assert not out["correct"], out["checks"]


# -- a program the lattice does not run, added as new files ------------------

QUAD = '''"""A toy program: gradient descent on the quadratic 0.5 * sum(h * x**2),
``rounds`` scanned steps a sweep, one cell per run seed, each starting from
the initial state plus a normal draw of its seed."""
import jax
import jax.numpy as jnp
import numpy as np

_TRACES = [0]


def make_data(config):
    return np.random.default_rng(config["data_seed"]).uniform(
        0.5, 1.5, config["dim"]).astype(np.float32)


def init_params(config, key):
    return jax.random.normal(key, (config["dim"],))


class Program:
    def __init__(self, config, traffic, data, params0):
        h, lr, rounds = jnp.asarray(data), config["lr"], traffic["rounds"]

        def run(x0, seeds):
            _TRACES[0] += 1

            def cell(seed):
                def step(x, _):
                    x = x - lr * h * x
                    return x, 0.5 * jnp.sum(h * x * x)
                x = x0 + jax.random.normal(jax.random.PRNGKey(seed), x0.shape)
                return jax.lax.scan(step, x, None, length=rounds)[1]
            return jax.vmap(cell)(seeds)

        self._run = jax.jit(run)
        self.params0 = params0

    def sweep(self, seeds):
        return self._run(self.params0, jnp.asarray(seeds, jnp.int32))


def n_cells(traffic):
    return traffic["seeds"]


def cell_rounds_per_sweep(traffic):
    return traffic["seeds"] * traffic["rounds"]


def kept(records):
    return np.asarray(records)


def counters():
    return None, _TRACES[0]


def release():
    pass


def check(config, traffic, data, params0, kept_list):
    """The losses again, in float64 on the host, from the same draws."""
    h = np.asarray(data, np.float64)
    readings = []
    for seeds, got in kept_list:
        x = np.stack([
            np.asarray(params0, np.float64)
            + np.asarray(jax.random.normal(jax.random.PRNGKey(s), params0.shape))
            for s in seeds
        ])
        want = []
        for _ in range(traffic["rounds"]):
            x = x - config["lr"] * h * x
            want.append(0.5 * np.sum(h * x * x, axis=1))
        want = np.stack(want, axis=1)
        readings.append({"loss_gap": float(np.max(np.abs(got - want) / want)),
                         "cells_compared": len(seeds)})
    return readings


def flops_per_cell_round(config, traffic):
    return 6.0 * config["dim"]
'''

# each a fault planted in the toy: (the text replaced, its replacement)
QUAD_FAULTS = {
    "check": ("want.append(0.5 * np.sum", "want.append(np.sum"),
    "answer": ("x = x - lr * h * x", "x = x - 0.5 * lr * h * x"),
}


def _toy_root(tmp_path, programs: dict):
    """A checkout with one cell per program of ``programs`` (name ->
    source), each with a configuration and traffic of its own, added as
    new files beside the repository's BENCHMARK.json entries."""
    root = tmp_path / "checkout"
    pb = root / "perfbench"
    for d in ("configs", "traffic", "programs"):
        (pb / d).mkdir(parents=True)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    os.symlink(os.path.join(ROOT, "perfbench", "metrics"), pb / "metrics")
    (pb / "traffic" / "toy.json").write_text(json.dumps({"seeds": 3, "rounds": 8}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, source in programs.items():
        if source is not None:
            (pb / "programs" / f"{name}.py").write_text(source)
        (pb / "configs" / f"{name}.json").write_text(json.dumps({
            "program": name, "dim": 64, "lr": 0.1, "data_seed": 0,
            "limits": {"loss_gap": 1e-4},
        }))
        bench["configs"].append({"name": name, "source": "test", "reduced": [],
                                 "file": f"perfbench/configs/{name}.json", "why": "test"})
        bench["workloads"].append({"name": name, "config": name, "traffic": "toy",
                                   "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    programs = {"quad": QUAD}
    for fault, (old, new) in QUAD_FAULTS.items():
        assert QUAD.count(old) == 1
        programs[f"quad_{fault}"] = QUAD.replace(old, new)
    programs["quad_missing"] = None
    programs["quad_partial"] = QUAD.replace("def kept(", "def _kept(").replace(
        "def release(", "def _release(")
    return _toy_root(tmp_path_factory.mktemp("toy"), programs)


def test_new_program_is_found_by_name_and_run_end_to_end(toy_root, capsys, repo_untouched):
    out = _drive(toy_root, capsys, workload="quad")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"cell_rounds_per_s", "peak_hbm_gib", "setup_s"}
    assert all(m["value"] >= 0 for m in out["metrics"].values())
    assert out["metrics"]["cell_rounds_per_s"]["value"] > 0
    # the toy's own check decided `correct`, over the warm-up and window sweeps
    assert [r["name"] for r in out["checks"]] == ["loss_gap"]
    assert out["checks"][0]["value"] < 1e-5
    assert out["attempted"] >= 2 and out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(QUAD_FAULTS))
def test_new_program_with_a_fault_is_not_correct(toy_root, capsys, repo_untouched, fault):
    out = _drive(toy_root, capsys, workload=f"quad_{fault}")
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("workload, named", [
    ("quad_missing", "quad_missing.py does not exist"),
    ("quad_partial", "lacks kept, release"),
])
def test_missing_or_incomplete_program_exits_nonzero(toy_root, capsys, repo_untouched,
                                                     workload, named):
    from perfbench.run import main

    rc = main(["--workload", workload, "--seed", "1", "--seconds", "0.2"],
              root=str(toy_root), require_tpu=False)
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert named in err


# the per-layer metrics read in every cell, whatever its program
LISTLESS = ("sweep_gap_ms", "compile_s", "window_compiles", "round_mfu_pct",
            "aircomp_roofline_pct", "aircomp_busy_pct", "device_idle_pct")


def test_listless_metrics_are_those_read_on_any_program():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert tuple(m["name"] for m in bench["per_layer"] if "workloads" not in m) == LISTLESS


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", LISTLESS)
def test_listless_reader_works_on_any_program(toy_root, repo_untouched, name, traced):
    from jax.profiler import ProfileData

    quad = load_program(str(toy_root), "quad")
    _, _, config, traffic = load_cell(str(toy_root), "quad")
    red = None
    if traced:  # the toy runs no fused aggregation kernel
        ops, spans = trace.events_of(ProfileData.from_text_proto(
            XSPACE.replace("aircomp_fused", "dot")))
        red = trace.reduce_events(ops, spans)
    compile_s, compiles = quad.counters()
    ctx = SimpleNamespace(
        red=red, config=config, traffic=traffic, chips=1,
        n_cells=quad.n_cells(traffic), rate=1234.5, compile_s=compile_s,
        window_compiles=compiles, device_kind="TPU v5 lite", program=quad,
    )
    value = load_metric(ROOT, name).read(ctx)
    assert value is None or (isinstance(value, (int, float)) and value == value)
    if name.startswith("aircomp_"):
        assert value is None
    if name in ("device_idle_pct", "sweep_gap_ms"):
        assert (value is not None) == traced


@pytest.mark.parametrize("part", ["make_data", "init_params", "Program", "forward_flops"])
def test_lattice_refuses_a_task_it_does_not_know(part):
    config = dict(_config("logreg-mnist-n30"), task="transformer")
    calls = {
        "make_data": lambda: LATTICE.make_data(config),
        "init_params": lambda: LATTICE.init_params(config, None),
        "Program": lambda: LATTICE.Program(config, {}, None, None),
        "forward_flops": lambda: LATTICE.forward_flops(config),
    }
    with pytest.raises(ValueError, match="transformer"):
        calls[part]()
