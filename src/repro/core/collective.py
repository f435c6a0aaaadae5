"""The AirComp superposition as a TPU collective (DESIGN.md §2).

Over-the-air computation exploits the MAC's superposition: every device
transmits simultaneously and the receiver observes the *sum*. On a TPU mesh
the identical computational pattern is a weighted ``psum`` over the
FL-device axes plus post-sum Gaussian noise — a *noisy all-reduce*:

    ŷ = Σ_i c_i · g_i + ν·z,   c_i = mask_i · ρ_i,  ν = sqrt(V_g)/a

Two call styles are provided:

  * :func:`aircomp_allreduce` — called *inside* an existing ``shard_map``
    body; this is the building block the distributed trainer composes.
  * :func:`make_sharded_aggregator` — builds a complete ``shard_map``-wrapped
    aggregator over a mesh for stacked per-device gradients (used in tests
    to validate agreement with the pure-jnp reference in core/aircomp.py).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def aircomp_allreduce(
    local_grads,
    coeff: jnp.ndarray,
    noise_amp: jnp.ndarray,
    key: jax.Array,
    axis_names: str | Sequence[str],
):
    """Noisy weighted all-reduce over ``axis_names`` (call inside shard_map).

    Args:
      local_grads: pytree of this slice's local gradients.
      coeff:       scalar c_i for this slice (0 if unscheduled).
      noise_amp:   scalar ν = sqrt(V_g)/a — receiver-noise amplitude.
      key:         PRNG key; must be *identical* across slices so every slice
                   adds the same receiver noise (the server noise is common).
    """
    leaves, treedef = jax.tree.flatten(local_grads)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys):
        summed = jax.lax.psum(leaf * coeff.astype(leaf.dtype), axis_names)
        noise = noise_amp.astype(leaf.dtype) * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(summed + noise)
    return jax.tree.unflatten(treedef, out)


def make_sharded_aggregator(mesh, axis_name: str = "data"):
    """shard_map aggregator for stacked per-device grads ``(N, D)``.

    N must equal the mesh axis size; device i's gradient lives on slice i.
    Returns ``fn(g, coeffs, noise_amp, key) -> (D,)`` with g sharded over
    the device axis — the distributed twin of ``aircomp.aircomp_aggregate``'s
    Eq. 16 path.
    """

    def body(g_local, coeffs_local, noise_amp, key):
        # g_local: (1, D) — this slice's device gradient; coeffs_local: (1,)
        y = aircomp_allreduce(
            g_local[0], coeffs_local[0], noise_amp, key, axis_name
        )
        return y[None, :]

    wrapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name), P(), P()),
        out_specs=P(axis_name, None),
        check_vma=False,
    )

    def agg(g, coeffs, noise_amp, key):
        out = wrapped(g, coeffs, noise_amp, key)
        return out[0]  # all slices hold the same psum result

    return agg


@partial(jax.jit, static_argnames=("axis_names",))
def _noop(x, axis_names):  # pragma: no cover - import-time sanity helper
    return x
