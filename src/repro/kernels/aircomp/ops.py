"""Public op: fused AirComp aggregation with automatic backend dispatch.

``use_pallas`` values:

  * ``'auto'``      — the compiled Pallas kernel on TPU, the pure-jnp
    reference elsewhere; off the TPU, the ``REPRO_PALLAS_INTERPRET=1`` env
    var selects interpret mode instead (the CPU parity path for the
    engine's ``pallas_fused`` aggregation backend). The var is read at
    TRACE time: set it before building engines/jits — already-compiled
    traces keep their mode (``sim.engine.cached_engine`` keys on it, so
    cached engines are safe; hand-built ``SimEngine``/lattice jits are
    not). On a TPU it is ignored: the chip always runs the kernel compiled.
  * ``True``        — the Pallas kernel (compiled).
  * ``'interpret'`` — the Pallas kernel in interpret mode (runs anywhere;
    slow — tests/parity only).
  * ``False``       — the pure-jnp reference.

Two entry points: :func:`aircomp_aggregate_fused` for a single round and
:func:`aircomp_aggregate_fused_batch` for a trial-batched lattice round
(leading ``n_trials`` axis on every argument — the shape ``repro.sim``'s
vmapped lattice produces per policy).
"""
from __future__ import annotations

import os

import jax

from repro.kernels.aircomp.kernel import (
    DEFAULT_TILE_D,
    aircomp_fused,
    aircomp_fused_batch,
)
from repro.kernels.aircomp.ref import aircomp_fused_batch_ref, aircomp_fused_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_mode(use_pallas: str | bool) -> str | bool:
    """Normalize a ``use_pallas`` argument to True / False / 'interpret'."""
    if use_pallas == "auto":
        if _on_tpu():
            return True
        return "interpret" if os.environ.get("REPRO_PALLAS_INTERPRET") else False
    return use_pallas


def aircomp_aggregate_fused(
    g, coeff, m_g, v_g, a, z, *, use_pallas: str | bool = "auto", tile_d: int = DEFAULT_TILE_D
):
    """Fused Eq. 5→8: ŷ = Σ_i coeff_i·(g_i − M_g) + sqrt(V_g)/a·z + M_g."""
    mode = resolve_mode(use_pallas)
    if mode == "interpret":
        return aircomp_fused(g, coeff, m_g, v_g, a, z, tile_d=tile_d, interpret=True)
    if mode:
        return aircomp_fused(g, coeff, m_g, v_g, a, z, tile_d=tile_d)
    return aircomp_fused_ref(g, coeff, m_g, v_g, a, z)


def aircomp_aggregate_fused_batch(
    g, coeff, m_g, v_g, a, z, *, use_pallas: str | bool = "auto", tile_d: int = DEFAULT_TILE_D
):
    """Trial-batched fused Eq. 5→8 over (n_trials, n_devices, D) gradients."""
    mode = resolve_mode(use_pallas)
    if mode == "interpret":
        return aircomp_fused_batch(g, coeff, m_g, v_g, a, z, tile_d=tile_d, interpret=True)
    if mode:
        return aircomp_fused_batch(g, coeff, m_g, v_g, a, z, tile_d=tile_d)
    return aircomp_fused_batch_ref(g, coeff, m_g, v_g, a, z)


__all__ = [
    "aircomp_aggregate_fused",
    "aircomp_aggregate_fused_batch",
    "aircomp_fused",
    "aircomp_fused_batch",
    "aircomp_fused_batch_ref",
    "aircomp_fused_ref",
]
