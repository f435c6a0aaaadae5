"""The per-device gradient block's layout between its product and its readers.

The canonical block is ``(N, D)``: each device's gradient leaves raveled in
``ravel_pytree`` order. For a 2-D weight whose minor axis is narrower than
one 128-lane tile (logreg's ``(784, 10)``), that order interleaves the
narrow axis into the lanes, while the gradient product writes the leaf with
its long axis in lanes. Raveling it therefore costs relayout copies of the
whole block, and the aggregation kernel's D tiles cost a pad on top.

The lane-dense carry keeps such a leaf as the product writes it: an
``(N, rows, L)`` segment, rows = the narrow axis in sublanes, L = the long
axis in lanes, one ``(rows, L)`` slab per device. (The TPU's batched
product puts its batch axis, the device, outermost; a segment with the
device axis in sublanes costs a relayout of the whole block again.) Every
other leaf stays in one flat ``(N, F)`` segment, in tree order. Three
passes read the block — :func:`block_stats`, the ``aircomp_fused`` kernel
and :func:`block_update_variance` — each over the segments as they lie. The
canonical order lives only on ``(D,)`` vectors: the noise draw is mapped
into the segments (:meth:`Layout.segments`) and ŷ back out
(:meth:`Layout.canonical`), so every coordinate meets the same sample and
the same arithmetic as in the flat block.

The rule reads leaf shapes only (:func:`carries_lane_dense`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import aircomp

LANE = 128  # TPU lane width


def carries_lane_dense(shape: tuple) -> bool:
    """A 2-D leaf narrower than one lane tile in its minor axis and at least
    one tile long in its major axis."""
    return len(shape) == 2 and shape[1] < LANE and shape[0] >= LANE


class GradBlock(NamedTuple):
    """Per-device gradients in segments: ``flat`` the ``(N, F)`` leaves that
    stay flat (None when every leaf is carried), ``dense`` one ``(N, rows,
    L)`` segment per carried leaf, tree order in both."""

    flat: jnp.ndarray | None
    dense: tuple


@dataclasses.dataclass(frozen=True)
class Layout:
    """Which leaves of a params pytree take the lane-dense carry."""

    shapes: tuple  # leaf shapes, tree order
    dense: tuple   # per leaf: carried lane-dense?

    @property
    def dim(self) -> int:
        return sum(math.prod(s) for s in self.shapes)

    @property
    def n_dense(self) -> int:
        return sum(self.dense)

    @property
    def dense_elems(self) -> int:
        return sum(math.prod(s) for s, d in zip(self.shapes, self.dense) if d)

    def block(self, grads) -> GradBlock:
        """Per-device gradient pytree (leaves ``(N, *shape)``) → segments."""
        leaves = jax.tree.leaves(grads)
        n = leaves[0].shape[0]
        flat = [g.reshape(n, -1) for g, d in zip(leaves, self.dense) if not d]
        dense = tuple(
            jnp.transpose(g, (0, 2, 1)) for g, d in zip(leaves, self.dense) if d
        )
        return GradBlock(
            flat=jnp.concatenate(flat, axis=1) if flat else None, dense=dense
        )

    def segments(self, vec: jnp.ndarray):
        """Canonical ``(D,)`` → (flat ``(F,)`` or None, ``(rows, L)`` per
        carried leaf)."""
        flat, dense, off = [], [], 0
        for shape, d in zip(self.shapes, self.dense):
            size = math.prod(shape)
            piece = vec[off:off + size]
            off += size
            if d:
                dense.append(piece.reshape(shape).T)
            else:
                flat.append(piece)
        return (jnp.concatenate(flat) if flat else None), tuple(dense)

    def canonical(self, flat: jnp.ndarray | None, dense: tuple) -> jnp.ndarray:
        """Inverse of :meth:`segments`."""
        pieces, off, it = [], 0, iter(dense)
        for shape, d in zip(self.shapes, self.dense):
            if d:
                pieces.append(next(it).T.reshape(-1))
            else:
                size = math.prod(shape)
                pieces.append(flat[off:off + size])
                off += size
        return jnp.concatenate(pieces)


def plan(params) -> Layout | None:
    """The layout for ``params``; None when no leaf qualifies."""
    shapes = tuple(tuple(jnp.shape(x)) for x in jax.tree.leaves(params))
    dense = tuple(carries_lane_dense(s) for s in shapes)
    return Layout(shapes=shapes, dense=dense) if any(dense) else None


def _segments(blk: GradBlock) -> list:
    """Every segment, device axis first."""
    return ([] if blk.flat is None else [blk.flat]) + list(blk.dense)


def _per_device(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over all but the leading (device) axis."""
    return jnp.sum(x, axis=tuple(range(1, x.ndim)))


def _by_device(v: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """An (N,) vector shaped to broadcast against a segment."""
    return v.reshape((-1,) + (1,) * (like.ndim - 1))


def block_stats(blk: GradBlock, dim: int) -> aircomp.GradStats:
    """:func:`aircomp.local_stats` over the segments: each device's sums run
    over all of its segments and divide by the true ``dim``."""
    segs = _segments(blk)
    mean = sum(_per_device(g) for g in segs) / dim
    var = sum(_per_device((g - _by_device(mean, g)) ** 2) for g in segs) / dim
    norm = jnp.sqrt(sum(_per_device(g * g) for g in segs))
    return aircomp.GradStats(mean=mean, var=var, norm=norm)


def block_update_variance(
    blk: GradBlock, rho: jnp.ndarray, mask: jnp.ndarray, data_frac: jnp.ndarray,
) -> jnp.ndarray:
    """``scheduling.global_update_variance`` over the segments."""
    total = 0.0
    zero = jnp.zeros((), jnp.float32)
    for g in _segments(blk):
        # both device sums in one variadic reduction: one read of the block
        est, target = jax.lax.reduce(
            (_by_device(rho * mask, g) * g, _by_device(data_frac, g) * g),
            (zero, zero),
            lambda x, y: (x[0] + y[0], x[1] + y[1]),
            (0,),
        )
        total = total + jnp.sum((est - target) ** 2)
    return total
