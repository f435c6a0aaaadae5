"""Round stages on the device and the benchmark readers that use them.

  * the op -> stage map (``repro.obs.stages``) that the engine registers at
    each lattice compile names all eight stages of a tiny CPU lattice, and
    survives ``reset_engine_cache``;
  * the named scopes change metadata only: a small lattice's records are
    bitwise the records of the program before the scopes (pinned hashes);
  * the per-layer readers of ``perfbench/metrics`` give hand-computed
    values on a synthetic trace reduction (``perfbench.trace.reduce_events``)
    with a registered map: two modules that share an op name, and idle
    intervals that span several host spans;
  * ``lattice.cache_misses`` counts a compile that missed the persistent
    compilation cache, and not one that loaded from it.
"""
from __future__ import annotations

import hashlib
import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import program_trace, trace  # noqa: E402
from perfbench.run import load_metric  # noqa: E402
from repro.obs import stages  # noqa: E402
from repro.obs.registry import counter_add, metric_value  # noqa: E402


def _tiny_lattice(backend="pallas_fused"):
    import jax
    import jax.numpy as jnp

    from repro.core.pofl import DeviceData, POFLConfig
    from repro.sim import LatticeSpec, run_lattice
    from repro.sim.tasks import TaskEval

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 20, 4))
    y = jax.random.randint(key, (8, 20), 0, 3)
    data = DeviceData(features=x, labels=y)
    params0 = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}

    def logits(p, fx):
        return fx @ p["w"] + p["b"]

    def loss_fn(p, fx, fy):
        logp = jax.nn.log_softmax(logits(p, fx))
        return -jnp.mean(jnp.take_along_axis(logp, fy[:, None], axis=1))

    spec = LatticeSpec(
        policies=("pofl", "channel"), noise_powers=(1e-11,), alphas=(0.1,),
        seeds=(0, 1), n_rounds=3, eval_every=2,
    )
    cfg = POFLConfig(
        n_devices=8, n_scheduled=3, backend=backend, simulate_physical=True
    )
    return lambda: run_lattice(
        loss_fn, data, params0, spec, base_cfg=cfg,
        eval_fn=TaskEval(logits, x[0], y[0], batch=20),
    )


# -- the op -> stage map -------------------------------------------------------

# ops that no stage's code emits: the PRNG's own key derivation, and
# primitives directly under the lattice program, its round loop or the
# loop's call (set-up, loop counter, record stacking, carry copies)
MACHINERY = re.compile(
    r"_threefry|_uniform"
    r"|^jit\([^)]*\)(/vmap\(\))*(/(while|body|cond|closed_call))*(/[a-z_]+)?$"
)
FREE = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


def _instructions(hlo_text):
    """name -> (opcode, op_name or None) of every instruction."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$", line)
        if m:
            opcode = re.search(r"(?:^|\s)([a-z][\w\-]*)\(", m.group(2)).group(1)
            op_name = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = (opcode, op_name.group(1) if op_name else None)
    return out


def test_stage_map_names_every_stage_of_a_tiny_lattice():
    from repro.sim.engine import latest_lattice_executable, reset_engine_cache

    _tiny_lattice()()
    assert "jit__fused_lattice_cell" in stages.registered_modules()
    text = latest_lattice_executable().as_text()
    reset_engine_cache()  # the harness resets before it reads metrics
    table = stages.stage_map("jit__fused_lattice_cell")
    assert table is not None
    assert set(stages.STAGES) <= set(table.values())
    # of the instructions that do work and come from the stages' code,
    # under 2% are left unscoped
    info = _instructions(text)
    work = {
        n: s for n, s in table.items()
        if info[n][0] not in FREE and info[n][1] and not MACHINERY.search(info[n][1])
    }
    unscoped = [n for n, s in work.items() if s == stages.UNSCOPED]
    assert len(work) > 50
    assert len(unscoped) < 0.02 * len(work), [(n, info[n]) for n in unscoped]


def test_stage_of_reads_through_transform_wrappers():
    assert stages.stage_of("jit(f)/vmap(local_update)/transpose(jvp())/mul") == "local_update"
    assert stages.stage_of("jit(f)/vmap()/while/body/closed_call/aggregation/"
                           "jit(aircomp_fused)/pallas_call") == "aggregation"
    assert stages.stage_of("jit(f)/eval/cond/branch_1_fun/statistics/x") == "eval"
    assert stages.stage_of("jit(f)/vmap()/while/body/dynamic_update_slice") is None


def test_stage_map_from_hlo_text_rules():
    text = """HloModule jit_m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inside = f32[4]{0} negate(%p), metadata={op_name="jit(m)/eval/neg"}
}

%body (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0)
  ROOT %copy.2 = f32[4]{0} copy(%c)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(m)/vmap(scheduling)/neg"}
  %copy.1 = f32[4]{0} copy(%fusion.1)
  %while.1 = f32[4]{0} while(%copy.1), condition=%cond, body=%body, metadata={op_name="jit(m)/channel/while"}
  ROOT %add.1 = f32[4]{0} add(%while.1, %x), metadata={op_name="jit(m)/while/body/add"}
}
"""
    table = stages.build_stage_map(text)
    assert table["inside"] == "eval"
    assert table["p"] == "scheduling"        # a fusion's input: the fusion's stage
    assert table["fusion.1"] == "scheduling"
    assert table["copy.1"] == "scheduling"   # no op_name: its operand's stage
    assert table["x"] == stages.UNSCOPED
    assert table["copy.2"] == "channel"      # the loop's carry: the loop's stage
    assert table["add.1"] == stages.UNSCOPED


@pytest.mark.parametrize("backend, digest", [
    ("jnp", "573d12a9d280300f17e0bf9601ce77ad925615412134cd777c9f59335581cd64"),
    ("pallas_fused", "364e46ef30d39a27b1d71533c31b7d75f28795f2a0633ea8ccee5973885c4916"),
])
def test_records_bitwise_as_before_the_scopes(backend, digest):
    """Hashes of the records of the same lattice recorded on the program
    before the stages had names."""
    recs = _tiny_lattice(backend)()
    h = hashlib.sha256()
    for f in ("e_com", "e_var", "grad_norm", "n_scheduled", "loss", "acc"):
        a = np.ascontiguousarray(np.asarray(getattr(recs, f)))
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == digest


# -- the readers, on a synthetic trace ----------------------------------------

MS = 1_000_000  # the timeline below is written in ms, the trace is in ns
ROUNDS = 2

HLO_A = """HloModule jit_a
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(a)/vmap(local_update)/dot_general"}
  ROOT %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%c, metadata={op_name="jit(a)/statistics/reduce_sum"}
}
"""
HLO_B = """HloModule jit_b
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(b)/aggregation/pallas_call"}
  %fusion.9 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%c, metadata={op_name="jit(b)/while/body/add"}
  ROOT %fusion.2 = f32[4]{0} fusion(%fusion.9), kind=kLoop, calls=%c, metadata={op_name="jit(b)/eval/dot_general"}
}
"""


def _ms(spans):
    return [(n, s * MS, e * MS) for n, s, e in spans]


DEVICE_OPS = {"/device:TPU:0": _ms([
    ("fusion.1", 8, 40), ("fusion.2", 40, 78),       # module jit_a
    ("convert.1", 95, 96),                            # in no module
    ("fusion.1", 118, 150), ("fusion.9", 150, 170),  # module jit_b
    ("fusion.2", 175, 188),
])}
MODULES = {"/device:TPU:0": _ms([
    ("jit_a", 8, 78), ("jit_b", 118, 188),
])}
HOST_SPANS = _ms([
    ("perfbench.sweep", 0, 100), ("perfbench.between", 100, 110),
    ("perfbench.sweep", 110, 200),
    ("lattice.sweep", 1, 99), ("lattice.place", 1, 5),
    ("lattice.dispatch", 5, 10), ("lattice.wait", 10, 80),
    ("lattice.stream_out", 80, 90), ("lattice.assemble", 90, 98),
    ("lattice.sweep", 111, 199), ("lattice.place", 111, 115),
    ("lattice.dispatch", 115, 120), ("lattice.wait", 120, 190),
    ("lattice.stream_out", 190, 195), ("lattice.assemble", 195, 198),
])


@pytest.fixture
def synthetic(monkeypatch):
    stages.register_module("jit_a", lambda: HLO_A)
    stages.register_module("jit_b", lambda: HLO_B)
    pt = program_trace.from_events(DEVICE_OPS, MODULES, HOST_SPANS)
    monkeypatch.setattr(program_trace, "current", lambda: pt)
    harness = [sp for sp in HOST_SPANS if sp[0].startswith("perfbench.")]
    ctx = SimpleNamespace(
        red=trace.reduce_events(DEVICE_OPS, harness), traffic={"rounds": ROUNDS},
    )
    yield pt, ctx
    stages.clear_modules()


def test_program_trace_names_ops_by_module(synthetic):
    pt, _ = synthetic
    (dev,) = pt.red.devices
    assert dev.op_ns == {
        "jit_a/fusion.1": 32 * MS, "jit_a/fusion.2": 38 * MS, "convert.1": 1 * MS,
        "jit_b/fusion.1": 32 * MS, "jit_b/fusion.9": 20 * MS, "jit_b/fusion.2": 13 * MS,
    }
    assert pt.n_sweeps == 2


def test_idle_split_at_span_boundaries(synthetic):
    pt, _ = synthetic
    (idle,) = program_trace.idle_ns(pt)
    assert {k: v / MS for k, v in idle.items()} == {
        "perfbench.sweep": 4, "perfbench.between": 10, "lattice.sweep": 2,
        "lattice.place": 8, "lattice.dispatch": 6, "lattice.wait": 9,
        "lattice.stream_out": 15, "lattice.assemble": 10,
    }


@pytest.mark.parametrize("metric, want", [
    ("local_update_ms", 32 / (2 * ROUNDS)),
    ("statistics_ms", 38 / (2 * ROUNDS)),
    ("aggregation_ms", 32 / (2 * ROUNDS)),
    ("eval_ms", 13 / (2 * ROUNDS)),
    ("unscoped_pct", 100 * 21 / 136),
    ("dispatch_idle_ms", (8 + 6) / 2),
    ("stream_out_idle_ms", (15 + 10) / 2),
])
def test_reader_values_by_hand(synthetic, metric, want):
    _, ctx = synthetic
    assert load_metric(ROOT, metric).read(ctx) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", [
    "local_update_ms", "statistics_ms", "aggregation_ms", "eval_ms",
    "unscoped_pct", "dispatch_idle_ms", "stream_out_idle_ms",
])
def test_readers_read_nothing_from_an_older_program(monkeypatch, metric):
    """No registered map and no program spans, as on a checkout before the
    stages had names: nothing to read, and nothing raised."""
    harness = [sp for sp in HOST_SPANS if sp[0].startswith("perfbench.")]
    pt = program_trace.from_events(DEVICE_OPS, MODULES, harness)
    monkeypatch.setattr(program_trace, "current", lambda: pt)
    ctx = SimpleNamespace(red=trace.reduce_events(DEVICE_OPS, harness),
                          traffic={"rounds": ROUNDS})
    assert load_metric(ROOT, metric).read(ctx) is None
    assert load_metric(ROOT, metric).read(SimpleNamespace(red=None)) is None


def test_cache_misses_reader_reads_the_counter():
    reader = load_metric(ROOT, "lattice_cache_misses")
    assert reader.read(None) is None  # an older program has no such counter
    counter_add("lattice.cache_misses", 0)
    assert reader.read(None) == 0


# -- lattice.cache_misses -------------------------------------------------------

@pytest.fixture
def cache_dir(tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs", "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cache_misses_one_cold_then_none_warm(cache_dir):
    from repro.sim.engine import reset_engine_cache

    run = _tiny_lattice()
    run()
    assert metric_value("lattice.n_compiles") == 1
    assert metric_value("lattice.cache_misses") == 1
    assert any(os.scandir(cache_dir))
    reset_engine_cache()  # a second engine compiles the same program anew
    run()
    assert metric_value("lattice.n_compiles") == 2
    assert metric_value("lattice.cache_misses") == 1
