"""Pure-jnp oracle for the fused AirComp aggregation kernel.

Computes the full Eq. 5→8 physical signal chain in one expression:

    ŷ[d] = Σ_i mask_i · ρ_i · (g_i[d] − M_g) + (sqrt(V_g)/a) · z[d] + M_g·Σ_i mask_i·ρ_i·0 ...

More precisely (matching core/aircomp.aircomp_aggregate simulate_physical=True
with real-valued effective channel after Lemma-1 inversion):

    s_i[d]  = (g_i[d] − M_g) / sqrt(V_g)                       (Eq. 5)
    y~[d]   = Σ_i mask_i · ρ_i · a · s_i[d] + z[d]             (Eq. 7, b_i h_i = ρ_i a)
    ŷ[d]    = sqrt(V_g)/a · y~[d] + M_g                        (Eq. 8)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.numerics import eps_guard


def aircomp_fused_batch_ref(g, coeff, m_g, v_g, a, z):
    """Trial-batched oracle: leading (n_trials,) axis on every argument.

    vmap of :func:`aircomp_fused_ref` — the reference for the batched Pallas
    kernel serving whole lattice batches.
    """
    return jax.vmap(aircomp_fused_ref)(g, coeff, m_g, v_g, a, z)


def aircomp_fused_ref(g, coeff, m_g, v_g, a, z):
    """Args:
      g:     (n_devices, D) stacked local gradients, or a lane-dense
             (n_devices, rows, L) segment
      coeff: (n_devices,)   mask_i · ρ_i
      m_g, v_g, a: scalars  (global mean/variance, denoise scalar)
      z:     (D,)           receiver noise ~ N(0, σ_z²), or (rows, L)
    Returns ŷ: (D,), or (rows, L)

    ``a`` is cancelled algebraically in the signal term — exactly as the
    Pallas kernel does — so an empty scheduled set (a=inf from the min over
    nothing, coeff all zero) stays finite: the naive a·s → (…)/a composition
    would produce 0·inf = NaN there.
    """
    sqrt_vg = jnp.sqrt(eps_guard(v_g))
    acc = jnp.sum(coeff.reshape((-1,) + (1,) * (g.ndim - 1)) * g, axis=0)  # Eq. 7, a cancelled
    w = jnp.sum(coeff)
    return acc - w * m_g + sqrt_vg / a * z + m_g  # Eq. 8
