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
