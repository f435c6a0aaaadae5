"""repro.obs flight-recorder suite (ISSUE 6 tentpole pin).

Layers under test:

  * registry/span/sink host-side plumbing — counter lifecycle, prefix reset
    scoping (the ``compile_cache.`` namespace must survive every reset the
    test fixtures perform), JSONL event stamping from the ``REPRO_DIST_*``
    contract without touching the jax backend;
  * the engine integration — ``engine_cache_stats`` /
    ``persistent_cache_counters`` as thin registry shims, lattice spans and
    ``lattice``-kind events, the warm-retrace report gate;
  * in-trace diagnostics — ``ObsConfig(diagnostics=True)`` returns the
    :class:`~repro.core.metrics.RoundDiagnostics` taps with UNCHANGED base
    records (OFF is bit-identical to the pre-obs program by construction —
    same trace; ON vs OFF is a cross-program comparison, so the base-record
    check is tight allclose, per the documented ≤1-ULP wobble), and a repeat
    diagnostics sweep re-traces zero times (the second engine-cache key);
  * the bench history satellite — ``benchmarks.run.append_history`` appends
    SHA+timestamp-stamped JSONL that ``benchmarks.report`` renders;
  * the ``@pytest.mark.distributed`` harness — a 2-process launcher run
    under one shared ``REPRO_OBS_DIR`` writes one event file per worker with
    consistent rank stamps and matching span totals.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import (
    ObsConfig,
    close_sink,
    counter,
    counter_add,
    emit,
    event_files,
    gauge,
    metric_value,
    metrics_snapshot,
    process_coords,
    read_events,
    reset_metrics,
    span,
    span_totals,
)
from repro.obs.report import collect, gate_warm_lattice, render
from repro.obs.report import main as report_main

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))


# --------------------------------------------------------------------------
# registry + spans + sink
# --------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    assert metric_value("t.c") == 0
    assert counter_add("t.c") == 1
    assert counter_add("t.c", 2.5) == 3.5
    c = counter("t.c")
    c.add(1)
    assert c.value == 4.5
    g = gauge("t.g")
    g.set(7)
    g.set(3)
    assert g.value == 3
    snap = metrics_snapshot("t.")
    assert snap == {"t.c": 4.5, "t.g": 3}


def test_reset_metrics_is_prefix_scoped():
    counter_add("ns1.a")
    counter_add("ns2.b")
    reset_metrics("ns1.")
    assert metric_value("ns1.a") == 0
    assert metric_value("ns2.b") == 1
    reset_metrics("ns2.")


def test_span_records_registry_totals_and_propagates_exceptions():
    with span("t.work") as s:
        pass
    assert s.seconds is not None and s.seconds >= 0
    with pytest.raises(ValueError, match="boom"):
        with span("t.work"):
            raise ValueError("boom")
    totals = span_totals("t.work")
    assert totals["count"] == 2
    assert totals["seconds"] >= 0

    @span("t.deco")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert span_totals("t.deco")["count"] == 1


def test_sink_inactive_without_env(monkeypatch):
    monkeypatch.delenv("REPRO_OBS_DIR", raising=False)
    ev = emit("counter", "t.quiet", delta=1, total=1)
    # the event dict is still assembled (registry callers rely on it) but
    # nothing is written anywhere
    assert ev["kind"] == "counter" and ev["name"] == "t.quiet"


def test_sink_writes_process_stamped_jsonl(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    # the rank stamp comes from the REPRO_DIST_* env contract, NOT from the
    # jax backend (the sink must stay importable/usable pre-init)
    monkeypatch.setenv("REPRO_DIST_PROCESS_ID", "1")
    monkeypatch.setenv("REPRO_DIST_NUM_PROCESSES", "2")
    assert process_coords() == (1, 2)
    with span("t.stamped", tag="x"):
        pass
    counter_add("t.stamped.extra")
    close_sink()
    files = event_files(str(tmp_path))
    assert len(files) == 1
    assert os.path.basename(files[0]).startswith("events-p001of002-")
    events = list(read_events(str(tmp_path)))
    assert {e["kind"] for e in events} == {"span", "counter"}
    for e in events:
        assert e["process_index"] == 1
        assert e["process_count"] == 2
        assert e["pid"] == os.getpid()
    (sp,) = [e for e in events if e["kind"] == "span"]
    assert sp["name"] == "t.stamped" and sp["tag"] == "x"


def test_read_events_skips_torn_lines(tmp_path):
    p = tmp_path / "events-p000of001-1.jsonl"
    p.write_text('{"kind": "counter", "name": "ok"}\n{"kind": "half\n\n')
    events = list(read_events(str(tmp_path)))
    assert len(events) == 1 and events[0]["name"] == "ok"


def test_sink_survives_killed_writer(tmp_path):
    """The resilience contract: a writer that dies hard (``os._exit``, as
    the ``REPRO_FAULT_KILL`` injection does — no atexit, no flush-on-close)
    loses at most the torn trailing line. Every event emitted before the
    kill must be durable on disk, and ``read_events`` must yield exactly
    those events past the tear."""
    script = (
        "import os\n"
        "os.environ['REPRO_OBS_DIR'] = r'%s'\n"
        "from repro.obs.sink import _handle, emit, obs_dir\n"
        "for i in range(3):\n"
        "    emit('heartbeat', 'killed.writer', i=i)\n"
        "h = _handle(obs_dir())\n"
        "h.write('{\"kind\": \"torn mid-li')\n"  # no newline: a torn write
        "os._exit(137)\n"
    ) % str(tmp_path)
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 137, proc.stderr[-2000:]
    events = [e for e in read_events(str(tmp_path)) if e["name"] == "killed.writer"]
    assert [e["i"] for e in events] == [0, 1, 2]


# --------------------------------------------------------------------------
# engine integration: shims, lifecycle, diagnostics
# --------------------------------------------------------------------------


def _tiny_task():
    import jax
    import jax.numpy as jnp

    from repro.core.pofl import DeviceData

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 20, 4))
    y = jax.random.randint(key, (8, 20), 0, 3)
    data = DeviceData(features=x, labels=y)
    params0 = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}

    def loss_fn(p, fx, fy):
        logits = fx @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, fy[:, None], axis=1))

    return loss_fn, data, params0


def _tiny_spec(n_rounds=3):
    from repro.sim.lattice import LatticeSpec

    return LatticeSpec(
        policies=("pofl", "channel"), noise_powers=(1e-11,), alphas=(0.1,),
        seeds=(0, 1), n_rounds=n_rounds,
    )


def test_engine_cache_stats_is_registry_shim():
    from repro.core.pofl import POFLConfig
    from repro.sim.engine import cached_engine, engine_cache_stats

    loss_fn, data, _ = _tiny_task()
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    assert engine_cache_stats() == {"hits": 0, "misses": 0, "size": 0}
    e1 = cached_engine(loss_fn, data, cfg)
    e2 = cached_engine(loss_fn, data, cfg)
    assert e1 is e2
    assert engine_cache_stats() == {"hits": 1, "misses": 1, "size": 1}
    assert metric_value("engine_cache.hits") == 1
    assert metric_value("engine_cache.misses") == 1


def test_counter_lifecycle_reset_scoping():
    """reset_engine_cache zeroes exactly the engine_cache. namespace; the
    process-lifetime compile_cache. counters survive every reset a test (or
    the autouse fixture) performs — the session-end cache report depends on
    that."""
    from repro.sim.compile_cache import persistent_cache_counters
    from repro.sim.engine import engine_cache_stats, reset_engine_cache

    before = persistent_cache_counters()
    counter_add("engine_cache.hits", 5)
    counter_add("span.fake.count", 2)
    reset_engine_cache()
    assert engine_cache_stats() == {"hits": 0, "misses": 0, "size": 0}
    assert metric_value("span.fake.count") == 2  # other namespaces untouched
    assert persistent_cache_counters() == before
    reset_metrics("span.")


def test_obs_config_is_second_engine_cache_key():
    from repro.core.pofl import POFLConfig
    from repro.sim.engine import cached_engine, engine_cache_stats

    loss_fn, data, _ = _tiny_task()
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    plain = cached_engine(loss_fn, data, cfg)
    diag = cached_engine(loss_fn, data, cfg, obs=ObsConfig(diagnostics=True))
    assert plain is not diag
    assert diag.obs.diagnostics
    # and the diagnostics engine is itself cached
    assert cached_engine(
        loss_fn, data, cfg, obs=ObsConfig(diagnostics=True)
    ) is diag
    assert engine_cache_stats()["misses"] == 2


def test_diagnostics_off_is_default_and_diag_is_none():
    from repro.sim.lattice import run_lattice
    from repro.core.pofl import POFLConfig

    loss_fn, data, params0 = _tiny_task()
    recs = run_lattice(
        loss_fn, data, params0, _tiny_spec(),
        base_cfg=POFLConfig(n_devices=8, n_scheduled=3),
    )
    assert recs.diag is None


def test_diagnostics_taps_values_and_unchanged_base_records():
    from repro.core.metrics import RoundDiagnostics
    from repro.core.pofl import POFLConfig
    from repro.sim.lattice import run_lattice

    loss_fn, data, params0 = _tiny_task()
    spec = _tiny_spec()
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    off = run_lattice(loss_fn, data, params0, spec, base_cfg=cfg)
    on = run_lattice(
        loss_fn, data, params0, spec, base_cfg=cfg,
        obs=ObsConfig(diagnostics=True),
    )
    # base records: ON vs OFF is a cross-program comparison (the taps change
    # the compiled program), so tight allclose rather than bitwise — the
    # documented cross-program reduction wobble
    for f in ("e_com", "e_var", "grad_norm", "n_scheduled"):
        np.testing.assert_allclose(
            getattr(on, f), getattr(off, f), rtol=1e-6, err_msg=f
        )
    d = on.diag
    assert isinstance(d, RoundDiagnostics)
    grid_shape = (1, len(spec.policies), 1, 1, 2, spec.n_rounds)
    for f in d._fields:
        tap = np.asarray(getattr(d, f))
        assert tap.shape == grid_shape, f
        assert np.isfinite(tap).all(), f
    # entropy of an 8-device scheduling distribution lives in [0, log 8]
    assert (d.sched_entropy >= 0).all()
    assert (d.sched_entropy <= np.log(8) + 1e-5).all()
    # no EPS guard should clamp on this benign task
    assert (d.eps_clamps == 0).all()
    assert (d.noise_eff >= 0).all()
    assert (d.grad_norm_spread >= 0).all()


def test_diagnostics_repeat_retraces_zero_times():
    import dataclasses

    from repro.core.pofl import POFLConfig
    from repro.sim.engine import FUSED_POLICY, cached_engine
    from repro.sim.lattice import run_lattice

    loss_fn, data, params0 = _tiny_task()
    spec = _tiny_spec()
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    obs = ObsConfig(diagnostics=True)
    first = run_lattice(loss_fn, data, params0, spec, base_cfg=cfg, obs=obs)
    eng = cached_engine(
        loss_fn, data, dataclasses.replace(cfg, policy=FUSED_POLICY), obs=obs
    )
    traces, compiles = eng.n_lattice_traces, eng.n_compiles
    assert traces == 1 and compiles == 1
    repeat = run_lattice(loss_fn, data, params0, spec, base_cfg=cfg, obs=obs)
    assert eng.n_lattice_traces == traces  # ISSUE 6 acceptance: zero retraces
    assert eng.n_compiles == compiles
    for f in repeat.diag._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(repeat.diag, f)), np.asarray(getattr(first.diag, f))
        )


def test_fallback_lattice_diagnostics_match_fused():
    from repro.core.pofl import POFLConfig
    from repro.sim.lattice import run_lattice

    loss_fn, data, params0 = _tiny_task()
    spec = _tiny_spec(n_rounds=2)
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    obs = ObsConfig(diagnostics=True)
    fused = run_lattice(loss_fn, data, params0, spec, base_cfg=cfg, obs=obs)
    fallback = run_lattice(
        loss_fn, data, params0, spec, base_cfg=cfg, obs=obs,
        fuse_policies=False,
    )
    for f in fused.diag._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fused.diag, f)),
            np.asarray(getattr(fallback.diag, f)),
            err_msg=f,
        )


def test_lattice_emits_events_and_gate_passes(monkeypatch, tmp_path):
    from repro.core.pofl import POFLConfig
    from repro.sim.lattice import run_lattice

    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    loss_fn, data, params0 = _tiny_task()
    spec = _tiny_spec(n_rounds=2)
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    run_lattice(loss_fn, data, params0, spec, base_cfg=cfg)
    run_lattice(loss_fn, data, params0, spec, base_cfg=cfg)  # warm repeat
    close_sink()

    summary = collect(read_events(str(tmp_path)))
    lat = summary["lattice"]
    assert len(lat) == 2
    cold, warm = lat
    assert cold["warm"] is False and cold["trace_delta"] == 1
    assert warm["warm"] is True and warm["trace_delta"] == 0
    assert warm["compile_delta"] == 0 and warm["engine_compiles"] == 1
    assert summary["spans"][(0, "lattice.sweep")]["count"] == 2
    assert summary["spans"][(0, "lattice.compile")]["count"] == 1
    assert gate_warm_lattice(summary) == []
    text = render(summary)
    assert "lattice.compile" in text and "lattice runs" in text
    # the module CLI agrees
    assert report_main([str(tmp_path), "--gate-warm-lattice"]) == 0


def test_report_gate_fails_on_warm_retrace(tmp_path, capsys):
    p = tmp_path / "events-p000of001-1.jsonl"
    bad = {
        "kind": "lattice", "name": "lattice.run", "process_index": 0,
        "cells": 4, "warm": True, "trace_delta": 1, "compile_delta": 1,
        "fused": True, "engine_compiles": 2,
    }
    p.write_text(json.dumps(bad) + "\n")
    assert report_main([str(tmp_path), "--gate-warm-lattice"]) == 1
    err = capsys.readouterr().err
    assert "re-traced" in err and "compiled programs" in err
    # and an empty sink dir is a gate failure too (nothing proven)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert report_main([str(empty), "--gate-warm-lattice"]) == 1


def test_run_with_history_counts_traces_in_registry():
    from repro.core.pofl import POFLConfig, run_pofl

    loss_fn, data, params0 = _tiny_task()
    cfg = POFLConfig(n_devices=8, n_scheduled=3, seed=0)
    assert metric_value("engine.traces") == 0
    run_pofl(loss_fn, params0, data, cfg, n_rounds=3)
    traces = metric_value("engine.traces")
    assert traces >= 1
    run_pofl(loss_fn, params0, data, cfg, n_rounds=3)  # cached: no retrace
    assert metric_value("engine.traces") == traces


# --------------------------------------------------------------------------
# spans on the profiler clock
# --------------------------------------------------------------------------

SWEEP_CHILDREN = (
    "lattice.place", "lattice.dispatch", "lattice.wait", "lattice.stream_out",
    "lattice.assemble",
)


def test_span_events_carry_their_parent(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    with span("t.outer") as outer:
        with span("t.inner") as inner:
            pass
        with span("t.inner"):
            pass
    with span("t.alone"):
        pass
    close_sink()
    assert outer.parent is None and inner.parent == "t.outer"
    got = [(e["name"], e["parent"]) for e in read_events(str(tmp_path))
           if e["kind"] == "span"]
    assert got == [("t.inner", "t.outer"), ("t.inner", "t.outer"),
                   ("t.outer", None), ("t.alone", None)]


def test_lattice_spans_land_in_a_profiler_trace(monkeypatch, tmp_path):
    """Under ``jax.profiler.trace`` every span of a sweep is an annotation
    on one host line, the five child spans inside ``lattice.sweep``, and
    the JSONL events name the same nesting."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core.pofl import POFLConfig
    from repro.sim.lattice import run_lattice

    loss_fn, data, params0 = _tiny_task()
    spec = _tiny_spec(n_rounds=2)
    cfg = POFLConfig(n_devices=8, n_scheduled=3)
    run_lattice(loss_fn, data, params0, spec, base_cfg=cfg)  # compile first
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path / "obs"))
    with jax.profiler.trace(str(tmp_path / "trace")):
        run_lattice(loss_fn, data, params0, spec, base_cfg=cfg)
    close_sink()

    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("lattice."):
                    lines.setdefault((plane.name, line.name), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    assert len(lines) == 1, lines  # one host line holds them all
    (events,) = lines.values()
    (sweep,) = [e for e in events if e[0] == "lattice.sweep"]
    children = sorted((e for e in events if e[0] != "lattice.sweep"), key=lambda e: e[1])
    assert [e[0] for e in children] == list(SWEEP_CHILDREN)
    for _, s, e in children:
        assert sweep[1] <= s <= e <= sweep[2]

    parents = {e["name"]: e["parent"] for e in read_events(str(tmp_path / "obs"))
               if e["kind"] == "span"}
    assert parents["lattice.sweep"] is None
    for name in SWEEP_CHILDREN:
        assert parents[name] == "lattice.sweep"


def test_report_names_each_spans_parent():
    events = [{"kind": "span", "name": "lattice.sweep", "seconds": 1.0, "parent": None}]
    events += [{"kind": "span", "name": n, "seconds": 0.1, "parent": "lattice.sweep"}
               for n in SWEEP_CHILDREN]
    events.append({"kind": "span", "name": "lattice.dispatch", "seconds": 0.1,
                   "parent": "resilience.chunk"})
    rows = {ln.split()[1]: ln for ln in render(collect(events)).splitlines()
            if ln.split()[1:2] and ln.split()[1].startswith("lattice.")}
    assert rows["lattice.sweep"].endswith("  -")
    for name in SWEEP_CHILDREN:
        assert rows[name].endswith("  lattice.sweep" if name != "lattice.dispatch"
                                   else "  lattice.sweep, resilience.chunk")


def test_import_repro_obs_leaves_jax_unimported():
    code = "import sys, repro.obs, repro.obs.stages; print('jax' in sys.modules)"
    out = subprocess.run(
        [sys.executable, "-c", code], env=dict(os.environ, PYTHONPATH=SRC),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# --------------------------------------------------------------------------
# bench history satellite
# --------------------------------------------------------------------------


def test_bench_history_append_and_report(tmp_path, capsys):
    from benchmarks.report import history_table, load_history
    from benchmarks.run import append_history

    path = str(tmp_path / "hist.jsonl")
    entry = append_history({"cells": 15, "steady_cells_per_sec": 42.0}, path=path)
    assert entry["git_sha"] and entry["timestamp"]
    append_history({"cells": 15, "steady_cells_per_sec": 43.5}, path=path)
    hist = load_history(path)
    assert len(hist) == 2
    assert hist[0]["cells"] == 15
    assert hist[1]["steady_cells_per_sec"] == 43.5
    table = history_table(hist)
    assert "42.0" in table and "43.5" in table
    assert hist[0]["git_sha"] == entry["git_sha"]
    assert load_history(str(tmp_path / "missing.jsonl")) == []


# --------------------------------------------------------------------------
# the 2-process shared-sink harness
# --------------------------------------------------------------------------


@pytest.mark.distributed
def test_two_process_workers_write_one_sink_file_each(tmp_path):
    """ISSUE 6 acceptance: a 2-process launcher parity run under one shared
    ``REPRO_OBS_DIR`` produces exactly one JSONL per worker (rank stamps
    {0, 1} of 2) with matching lattice span/compile totals across ranks —
    SPMD workers run the same program, so their flight recordings agree."""
    obs_dir = str(tmp_path / "obs")
    out = str(tmp_path / "parity.npz")
    env = dict(
        os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu", REPRO_OBS_DIR=obs_dir
    )
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.distributed",
         "--procs", "2", "--devices-per-proc", "4",
         "--workload", "parity", "--out", out, "--n-rounds", "2",
         "--timeout", "450"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout[-8000:])
        sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "distributed launcher failed"

    files = event_files(obs_dir)
    assert len(files) == 2, files
    names = sorted(os.path.basename(f) for f in files)
    assert names[0].startswith("events-p000of002-")
    assert names[1].startswith("events-p001of002-")

    summary = collect(read_events(obs_dir))
    assert summary["processes"] == {0, 1}
    per_rank = {}
    for rank in (0, 1):
        per_rank[rank] = {
            "compiles": summary["spans"].get((rank, "lattice.compile"), {}).get("count", 0),
            "sweeps": summary["spans"].get((rank, "lattice.sweep"), {}).get("count", 0),
            "gathers": summary["spans"].get((rank, "multihost.gather"), {}).get("count", 0),
            "lattice_events": [
                (e["warm"], e["trace_delta"]) for e in summary["lattice"]
                if e["process_index"] == rank
            ],
        }
    # SPMD: every rank compiled/swept/gathered the same number of times and
    # recorded the same cold/warm lattice sequence
    assert per_rank[0] == per_rank[1]
    assert per_rank[0]["sweeps"] == 3  # cold + warm repeat + fallback
    assert per_rank[0]["gathers"] >= 3
    # the warm repeat re-traced zero times on BOTH ranks
    assert gate_warm_lattice(summary) == []
