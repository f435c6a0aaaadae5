"""AOT compiles of the ``aircomp`` kernel for a described TPU v5e chip.

Interpret mode cannot see what the TPU lowering refuses (block shapes off
the (8, 128) tiling, too much VMEM), so the kernels of the lattice's main
path are compiled here for a chip that is described, not attached — at the
default benchmark cell (15 cells x N = 20 x D = 7,850) and the paper-width
CNN cell (15 x 30 x 258,634) — and the compiled HLO must hold the kernel
(``tpu_custom_call``). Nothing runs; no chip is needed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aircomp import aircomp_aggregate_fused, aircomp_fused_batch

SHAPES = [(15, 20, 7850), (15, 30, 258634)]  # (cells, N, D)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to a persistent cache but
    cannot be read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _lattice_op(g, coeff, m_g, v_g, a, z):
    """The aggregation stage's call, batched over cells as the lattice
    batches it."""
    one = functools.partial(aircomp_aggregate_fused, use_pallas=True)
    return jax.vmap(one)(g, coeff, m_g, v_g, a, z)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize(
    "op", [_lattice_op, aircomp_fused_batch], ids=["cell_vmap", "batch"]
)
def test_aircomp_compiles_for_v5e(op, shape, one_chip, no_compile_cache):
    cells, n, d = shape

    def sds(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    compiled = jax.jit(op).lower(
        sds(cells, n, d), sds(cells, n), sds(cells), sds(cells), sds(cells),
        sds(cells, d),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _logreg_lattice_hlo(one_chip, monkeypatch, cells: int) -> str:
    """The optimized HLO of a fused logreg lattice (``cells`` cells, N = 30,
    D = 7,850, 3 rounds) compiled for the described chip."""
    import numpy as np

    import repro.kernels.aircomp.ops as ops
    from repro.core.pofl import DeviceData, POFLConfig
    from repro.models import small
    from repro.sim.engine import FUSED_POLICY, cached_engine
    from repro.sim.tasks import TaskEval

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n, n_samples, rounds = 30, 20, 3
    rng = np.random.default_rng(0)
    fx = jnp.asarray(rng.standard_normal((n, n_samples, 784)), jnp.float32)
    fy = jnp.asarray(rng.integers(0, 10, (n, n_samples)), jnp.int32)
    cfg = POFLConfig(
        n_devices=n, n_scheduled=10, batch_size=10, backend="pallas_fused",
        simulate_physical=True, policy=FUSED_POLICY,
    )
    eng = cached_engine(
        small.logreg_loss, DeviceData(features=fx, labels=fy), cfg,
        eval_fn=TaskEval(small.logreg_logits, fx[0], fy[0], batch=n_samples),
    )

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return eng._fused_lattice_jit.lower(
        {"w": sds((784, 10)), "b": sds((10,))}, sds((rounds,), jnp.int32),
        sds((rounds,), jnp.bool_), sds((cells,)), sds((cells,)),
        sds((cells,), jnp.int32), sds((cells,), jnp.int32),
    ).compile().as_text()


def _hlo_instructions(text: str):
    """(computation -> its instruction lines, instruction -> its text after
    ``=``, instruction -> its computation)."""
    import re

    bodies, current, lines, where = {}, None, {}, {}
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$", line)
        if head:
            current = bodies.setdefault(head.group(1), [])
            name = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$", line)
        if m and current is not None:
            current.append(line)
            lines[m.group(1)] = m.group(2)
            where[m.group(1)] = name
    return bodies, lines, where


def _output_dims(rest: str, opcode: str) -> list:
    """The dimensions of each array an ``opcode`` instruction outputs."""
    import re

    if f" {opcode}(" not in rest:
        return []
    return re.findall(r"\w+\[([\d,]*)\]", rest.split(f" {opcode}(")[0])


def test_lattice_stages_named_in_the_v5e_program(one_chip, no_compile_cache, monkeypatch):
    """A small fused lattice (8 cells, N = 30, D = 7,850) compiled for the
    described chip: the kernel keeps the name the benchmark's kernel
    metrics find it by and sits in the ``aggregation`` stage, and the
    mini-batch gather and the gradient product belong to ``local_update``."""
    import re

    from repro.obs.stages import build_stage_map

    text = _logreg_lattice_hlo(one_chip, monkeypatch, cells=8)
    table = build_stage_map(text)
    bodies, lines, _ = _hlo_instructions(text)

    def fused(rest):
        called = re.search(r"calls=%([^\s,}]+)", rest)
        return "\n".join(bodies.get(called.group(1), [])) if called else ""

    (kernel,) = [n for n, r in lines.items() if 'custom_call_target="tpu_custom_call"' in r]
    assert kernel.startswith("aircomp_fused")
    assert "/aggregation/" in re.search(r'op_name="([^"]*)"', lines[kernel]).group(1)
    assert table[kernel] == "aggregation"

    gathers = [n for n, r in lines.items()
               if any(d.endswith(",784") for d in _output_dims(r, "fusion"))
               and "gather(" in fused(r)]
    dots = [n for n, r in lines.items()
            if any(re.search(r",(784,10|10,784)$", d) for d in _output_dims(r, "fusion"))
            and "convolution(" in fused(r)]
    assert gathers and dots
    for name in gathers + dots:
        assert table[name] == "local_update", (name, lines[name][:200])


def test_gradient_block_written_once_on_v5e(one_chip, no_compile_cache, monkeypatch):
    """The cell-shaped round (15 cells, N = 30, D = 7,850) compiled for the
    described chip carries the weight gradient lane-dense: the kernel
    aggregates it, and no copy under ``local_update`` nor pad under
    ``aggregation`` writes a block of cells x N x 7,840 elements or more
    between the product and its readers."""
    import math
    import re

    cells, n = 15, 30
    text = _logreg_lattice_hlo(one_chip, monkeypatch, cells=cells)
    assert "tpu_custom_call" in text
    _, lines, where = _hlo_instructions(text)
    fused = {c for r in lines.values() for c in re.findall(r"calls=%([^\s,}]+)", r)}
    big = []
    for name, rest in lines.items():
        if where[name] in fused:  # a fusion's body: its fusion is the op
            continue
        op_name = re.search(r'op_name="([^"]*)"', rest)
        op_name = op_name.group(1) if op_name else ""
        for opcode, stage in (("copy", "/local_update/"), ("pad", "/aggregation/")):
            sizes = [math.prod(int(x) for x in d.split(",") if x)
                     for d in _output_dims(rest, opcode)]
            if stage in op_name and any(s >= cells * n * 7840 for s in sizes):
                big.append((name, rest[:160]))
    assert not big, big
