"""Fused AirComp aggregation — Pallas TPU kernel.

The paper's per-parameter hot loop (Eqs. 5→8) touches every gradient element
five times when written naively (normalize, transmit-scale, superpose,
denoise, denormalize). The fused kernel makes ONE pass over HBM:

    ŷ[d] = Σ_i coeff_i·g_i[d] − W·M_g + (sqrt(V_g)/a)·z[d] + M_g,
    W = Σ_i coeff_i

(the algebraic collapse of Eq. 5 normalize → Lemma-1 transmit scale →
Eq. 7 superpose → Eq. 8 denoise/denormalize), computed tile-by-tile with the
(n_devices, TILE_D) gradient block resident in VMEM. VPU-bound (no MXU): the
roofline term is HBM bytes, so the single-pass fusion is the whole win —
~5× fewer HBM touches than the composed elementwise chain.

TPU layout notes:
  * TILE_D is a multiple of 128 (lane dimension).
  * n_devices (≤ a few hundred in FL) sits in the sublane dimension; the
    device reduction is a VPU cross-sublane sum.
  * scalars (M_g, V_g, a, W) ride in SMEM as one (1, 4) block, which
    stays a legal block shape when the lattice vmaps the call.
  * a lane-dense segment ``(N, rows, L)`` (``core.grad_layout``: a narrow
    weight's gradient as its product writes it) is read as it lies: each
    block holds every device and row and the full L when that fits
    ``_ROWS_BLOCK_BYTES``, so nothing is padded and a cell is one grid
    step; the device sum adds whole ``(rows, T)`` slabs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import eps_guard

DEFAULT_TILE_D = 512

# TPU lane width: tiles stay multiples of this when clamping
_LANE = 128
# TPU sublane count of a float32 tile
_SUBLANE = 8
# VMEM budget of one (N, rows, tile) gradient block of the lane-dense form;
# double-buffered it stays far inside the default scoped VMEM
_ROWS_BLOCK_BYTES = 2 << 20


def _clamp_tile(d: int, tile_d: int) -> int:
    """Clamp an oversized tile down toward D (rounded up to the 128-lane
    multiple) so a small D — e.g. a shard-local block under the lattice's
    2-D (cells × model) mesh — pads to one snug tile instead of a mostly
    dead ``tile_d``-wide grid. A caller-requested tile smaller than the
    aligned D passes through untouched (tests drive tiny tiles on purpose).
    """
    aligned = -(-d // _LANE) * _LANE
    return min(tile_d, aligned)


def _aircomp_kernel(scalars_ref, coeff_ref, g_ref, z_ref, out_ref):
    m_g = scalars_ref[0, 0]
    v_g = scalars_ref[0, 1]
    a = scalars_ref[0, 2]
    w = scalars_ref[0, 3]  # Σ_i coeff_i

    g = g_ref[...].astype(jnp.float32)          # (N, T)
    z = z_ref[...].astype(jnp.float32)          # (1, T)
    coeff = coeff_ref[...].astype(jnp.float32)  # (N, 1)

    sqrt_vg = jax.lax.sqrt(eps_guard(v_g))
    acc = jnp.sum(coeff * g, axis=0, keepdims=True)  # (1, T)
    out_ref[...] = (acc - w * m_g + (sqrt_vg / a) * z + m_g).astype(out_ref.dtype)


def _aircomp_rows_kernel(scalars_ref, coeff_ref, g_ref, z_ref, out_ref):
    """:func:`_aircomp_kernel` over an ``(N, rows, T)`` block: the same
    arithmetic per element, the device sum over the block's major axis
    (``coeff`` rides in SMEM, one scalar per device slab)."""
    m_g = scalars_ref[0, 0]
    v_g = scalars_ref[0, 1]
    a = scalars_ref[0, 2]
    w = scalars_ref[0, 3]

    z = z_ref[...].astype(jnp.float32)  # (R, T)

    def add_device(i, acc):
        return acc + coeff_ref[0, i] * g_ref[i].astype(jnp.float32)

    sqrt_vg = jax.lax.sqrt(eps_guard(v_g))
    acc = jax.lax.fori_loop(
        0, g_ref.shape[0], add_device, jnp.zeros(z.shape, jnp.float32), unroll=True
    )  # (R, T)
    out_ref[...] = (acc - w * m_g + (sqrt_vg / a) * z + m_g).astype(out_ref.dtype)


def _rows_tile(n: int, rows: int, length: int) -> int:
    """Lane tile of an ``(n, rows, length)`` segment: the full length when
    the tile-padded block fits ``_ROWS_BLOCK_BYTES``, else the widest
    multiple of 128 lanes that does (the grid then ends in a ragged tile)."""
    sub = -(-rows // _SUBLANE) * _SUBLANE
    per_lane_tile = n * sub * _LANE * 4
    if -(-length // _LANE) * per_lane_tile <= _ROWS_BLOCK_BYTES:
        return length
    return max(1, _ROWS_BLOCK_BYTES // per_lane_tile) * _LANE


def _aircomp_rows(g, coeff, scalars, z, interpret):
    n, rows, length = g.shape
    tile = _rows_tile(n, rows, length)
    return pl.pallas_call(
        _aircomp_rows_kernel,
        grid=(pl.cdiv(length, tile),),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n, rows, tile), lambda i: (0, 0, i)),  # gradient block
            pl.BlockSpec((rows, tile), lambda i: (0, i)),        # noise block
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, length), g.dtype),
        interpret=interpret,
    )(scalars, coeff[None, :], g, z)


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def aircomp_fused(
    g: jnp.ndarray,       # (n_devices, D), or (n_devices, rows, L)
    coeff: jnp.ndarray,   # (n_devices,)  mask_i · ρ_i
    m_g: jnp.ndarray,     # scalar
    v_g: jnp.ndarray,     # scalar
    a: jnp.ndarray,       # scalar
    z: jnp.ndarray,       # (D,), or (rows, L)
    *,
    tile_d: int = DEFAULT_TILE_D,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused Eq. 5→8 aggregation. Returns ŷ of shape (D,), or (rows, L)
    for a lane-dense ``(n_devices, rows, L)`` segment, read unpadded
    (``tile_d`` applies to the flat form only).

    D is padded to a multiple of ``tile_d`` internally; a ``tile_d`` wider
    than (128-lane-aligned) D is clamped first, so shard-local blocks of a
    model-sharded lattice launch a snug grid rather than padding to the
    default tile.

    Batching: under ``jax.vmap`` (the lattice's cell axis) Pallas prepends
    a grid dimension and a squeezed leading block dimension to every
    operand. Each block therefore keeps its last two dimensions equal to
    the array's own — the scalars ride as a ``(1, 4)`` SMEM block, not a
    whole ``(4,)`` vector, whose batched ``(cells, 4)`` form the TPU
    lowering refuses.
    """
    scalars = jnp.stack(
        [m_g.astype(jnp.float32), v_g.astype(jnp.float32),
         a.astype(jnp.float32), jnp.sum(coeff).astype(jnp.float32)]
    )[None, :]
    if g.ndim == 3:
        return _aircomp_rows(g, coeff, scalars, z, interpret)

    n, d = g.shape
    tile_d = _clamp_tile(d, tile_d)
    d_pad = ((d + tile_d - 1) // tile_d) * tile_d
    if d_pad != d:
        g = jnp.pad(g, ((0, 0), (0, d_pad - d)))
        z = jnp.pad(z, (0, d_pad - d))

    out = pl.pallas_call(
        _aircomp_kernel,
        grid=(d_pad // tile_d,),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),       # coeff column
            pl.BlockSpec((n, tile_d), lambda i: (0, i)),  # gradient tile
            pl.BlockSpec((1, tile_d), lambda i: (0, i)),  # noise tile
        ],
        out_specs=pl.BlockSpec((1, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), g.dtype),
        interpret=interpret,
    )(scalars, coeff[:, None], g, z[None, :])
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def aircomp_fused_batch(
    g: jnp.ndarray,       # (n_trials, n_devices, D)
    coeff: jnp.ndarray,   # (n_trials, n_devices)  mask_i · ρ_i per trial
    m_g: jnp.ndarray,     # (n_trials,)
    v_g: jnp.ndarray,     # (n_trials,)
    a: jnp.ndarray,       # (n_trials,)
    z: jnp.ndarray,       # (n_trials, D)
    *,
    tile_d: int = DEFAULT_TILE_D,
    interpret: bool = False,
) -> jnp.ndarray:
    """Trial-batched fused Eq. 5→8 aggregation — one kernel launch serves a
    whole lattice batch. Returns ŷ of shape (n_trials, D).

    The ``vmap`` of :func:`aircomp_fused`: the grid becomes
    (n_trials, D/tile_d), so each (N, TILE_D) gradient block is loaded from
    HBM exactly once and each trial's scalars sit in SMEM — the same
    program the lattice's cell ``vmap`` builds around the single-round op.
    """
    one = functools.partial(aircomp_fused, tile_d=tile_d, interpret=interpret)
    return jax.vmap(one)(g, coeff, m_g, v_g, a, z)
