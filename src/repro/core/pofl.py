"""PO-FL — Algorithm 1: the faithful over-the-air FL simulator.

This is the paper's training loop at paper scale (N≈30 devices, vmap over
devices). Every step of Algorithm 1 is implemented:

  1. broadcast w^t                      (implicit — shared params)
  2. local mini-batch gradients g_i^t   (vmap of jax.grad over devices)
  3. upload scalar stats M_i, V_i, ||g_i||
  4. server computes p_i^t (scheduling.py), samples S^t, broadcasts stats
  5. devices normalize + transmit concurrently; server denoises (aircomp.py)
  6. w^{t+1} = w^t − η^t ŷ^t

The round body is an explicit **pipeline of composable stages**

    local_update_stage → scheduling_stage → aggregation_stage → apply_update_stage

(``core.local_update``'s :func:`~repro.core.local_update.local_update_stage`
generalizes the historical single-gradient ``local_gradient_stage`` —
re-exported here unchanged — to ``cfg.local_steps`` local SGD steps under a
``cfg.local_algorithm`` ∈ {fedavg, fedprox, feddyn, scaffold} branch table;
the default ``fedavg``/``local_steps=1`` traces the EXACT legacy program)
composed by :func:`round_algorithm` so that the legacy per-round jit
(:func:`make_round_step`), the scanned simulation engine
(``repro.sim.engine``) and the lattice all execute the *same* traced
computation. The transmit/aggregate stage is parameterized by an
:class:`AggregationBackend`:

  * ``jnp``           — the exact reference path (Eq. 16 / full Eq. 5→8,
    per ``cfg.simulate_physical``); the default, bit-identical to the seed.
  * ``pallas_fused``  — the one-HBM-pass fused Eq. 5→8 kernel
    (``kernels/aircomp``): the compiled Pallas kernel on TPU, its pure-jnp
    oracle elsewhere, interpret mode off the TPU via
    ``REPRO_PALLAS_INTERPRET=1`` (parity path).
    Semantics are the *physical* chain (algebraically equal to
    ``simulate_physical=True``; differs from Eq. 16 by ``(1−Σρ)·M_g``).

Data may be heterogeneous: :class:`DeviceData` optionally carries per-device
sample counts ``n_samples`` (shards padded to a common length), and the
m_i/M weights of Eq. 34/35/37 follow the true fractions. ``run_pofl`` is a
thin compatibility wrapper over the engine (identical trajectories for
identical seeds — pinned by tests/test_sim.py) with engine/jit caching
across calls keyed by (task, cfg-minus-seed, backend).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import aircomp, grad_layout, scheduling
from repro.core.channel import ChannelConfig, ChannelState
from repro.core.local_update import (  # noqa: F401  (re-exported API)
    STATELESS,
    AlgState,
    local_gradient_block,
    local_gradient_stage,
    local_update_stage,
)
from repro.core.metrics import RoundHealth, RoundMetrics, diagnostics_taps
from repro.core.numerics import safe_div


class AggregationBackend(str, enum.Enum):
    """How the transmit/aggregate stage realizes the Eq. 5→8 signal chain."""

    JNP = "jnp"                    # exact reference (Eq. 16 or full Eq. 5→8)
    PALLAS_FUSED = "pallas_fused"  # fused one-pass kernel (physical semantics)


BACKENDS = tuple(b.value for b in AggregationBackend)


@dataclasses.dataclass(frozen=True)
class ModelShard:
    """Model-dimension sharding context for the round pipeline.

    Built by ``repro.sim.engine.SimEngine`` when its mesh carries a
    ``"model"`` axis of size > 1 (a 2-D ``("cells", "model")`` mesh from
    ``repro.sim.lattice.make_cell_model_mesh``). When threaded into
    :func:`round_algorithm` it reroutes the D-elementwise hot path through
    ``shard_map`` over the model axis:

      * the flat (N, D) gradient block is zero-padded to a multiple of
        ``|model| · tile_d`` and constrained to ``P(None, "model")`` — each
        device holds only its own ``D/|model|`` columns;
      * the Eq. 5 statistics M_i, V_i, ||g_i|| become small ``psum``\\ s of
        shard-local partial sums over the model axis (padding columns are
        masked out, so values match the unsharded stats up to reduction
        order);
      * the aggregation stage runs shard-locally on each
        ``(n_devices, D_local)`` block — the fused Pallas kernel's grid is
        aligned to the shard (``kernels/aircomp`` clamps its tile to the
        block), the ``jnp`` reference uses the identical factored-out
        :func:`repro.core.aircomp.combine_given_stats` — with no collective
        at all: the device-axis reduction is elementwise over D;
      * the updated params carry is constrained back to its model-sharded
        placement (``repro.launch.sharding.param_spec``) so the scan carry
        keeps a stable sharding across rounds.

    Everything outside that path (scheduling, channel, PRNG discipline,
    e_com's closed form over the TRUE dim) is untouched, and ``None`` — the
    default everywhere — leaves the traced program bit-identical to the
    unsharded engine.
    """

    mesh: Any          # jax.sharding.Mesh with a "model" axis of size > 1
    axis: str = "model"

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def padded_dim(self, dim: int) -> int:
        """D rounded up so every model shard holds a whole number of default
        kernel tiles (the fused kernel then launches a snug, pad-free grid
        on its local block)."""
        from repro.kernels.aircomp import DEFAULT_TILE_D  # late: kernels↔core

        unit = self.n_shards * DEFAULT_TILE_D
        return -(-dim // unit) * unit

    def pad_features(self, g: jnp.ndarray, dim: int) -> jnp.ndarray:
        """Zero-pad the trailing (flat-D) axis to :meth:`padded_dim` and
        constrain it to ``P(None, "model")`` placement."""
        d_pad = self.padded_dim(dim)
        if d_pad != dim:
            g = jnp.pad(g, ((0, 0), (0, d_pad - dim)))
        return jax.lax.with_sharding_constraint(
            g, NamedSharding(self.mesh, P(None, self.axis))
        )

    def leaf_sharding(self, shape) -> NamedSharding:
        """The params-leaf placement rule (reuses the dormant FSDP machinery:
        last dim divisible by |model| → "model"; tiny leaves replicated)."""
        from repro.launch.sharding import param_spec  # late: launch↔core

        return NamedSharding(self.mesh, param_spec(tuple(shape), self.mesh))


def _model_sharded_local_stats(
    ms: ModelShard, g_pad: jnp.ndarray, dim: int
) -> aircomp.GradStats:
    """Step-3 statistics over a model-sharded padded gradient block.

    Each shard reduces its own columns; only the three (N,)-sized partial
    sums cross the model axis (the "small psums" of the 2-D lattice). The
    zero-padding columns are masked out of every sum — when D divides the
    shard count the mask is all-ones and the arithmetic is a pure
    sum-then-divide, matching :func:`aircomp.local_stats` up to the
    documented cross-program reduction-order wobble.
    """
    ax = ms.axis

    def stats_block(gb):
        d_local = gb.shape[-1]
        col0 = jax.lax.axis_index(ax) * d_local
        valid = ((col0 + jnp.arange(d_local)) < dim).astype(gb.dtype)
        gv = gb * valid
        mean = jax.lax.psum(jnp.sum(gv, axis=-1), ax) / dim
        dev = (gb - mean[:, None]) * valid
        var = jax.lax.psum(jnp.sum(dev * dev, axis=-1), ax) / dim
        norm = jnp.sqrt(jax.lax.psum(jnp.sum(gv * gv, axis=-1), ax))
        return mean, var, norm

    mean, var, norm = jax.shard_map(
        stats_block, mesh=ms.mesh,
        in_specs=(P(None, ax),), out_specs=(P(), P(), P()),
        check_vma=False,
    )(g_pad)
    return aircomp.GradStats(mean=mean, var=var, norm=norm)


def _model_sharded_combine(
    cfg: "POFLConfig",
    ms: ModelShard,
    g_pad: jnp.ndarray,
    rho: jnp.ndarray,
    h: jnp.ndarray,
    mask: jnp.ndarray,
    z_pad: jnp.ndarray,
    m_g: jnp.ndarray,
    v_g: jnp.ndarray,
    a: jnp.ndarray,
    use_pallas: str | bool,
) -> jnp.ndarray:
    """Shard-local Eq. 5→8 combine: every input except the D-sharded
    gradient/noise blocks is replicated, the output is the D-sharded ŷ, and
    no collective runs inside — the device-axis reduction is elementwise
    over D. The fused kernel launches per shard on its local
    ``(n_devices, D_local)`` block (its grid aligned to the shard); the jnp
    backend runs the identical factored-out reference arithmetic."""
    backend = AggregationBackend(cfg.backend)
    if backend is AggregationBackend.JNP:

        def agg_block(gb, zb, rho_, h_, mask_, m_g_, v_g_, a_):
            return aircomp.combine_given_stats(
                gb, rho_, h_, mask_, zb, m_g_, v_g_, a_,
                simulate_physical=cfg.simulate_physical,
            )

    else:
        from repro.kernels.aircomp import aircomp_aggregate_fused  # late

        def agg_block(gb, zb, rho_, h_, mask_, m_g_, v_g_, a_):
            coeff = mask_ * rho_  # b_i h_i = ρ_i a exactly (Lemma 1)
            return aircomp_aggregate_fused(
                gb, coeff, m_g_, v_g_, a_, zb, use_pallas=use_pallas
            )

    ax = ms.axis
    return jax.shard_map(
        agg_block, mesh=ms.mesh,
        in_specs=(
            P(None, ax), P(ax), P(None), P(None), P(None), P(), P(), P(),
        ),
        out_specs=P(ax), check_vma=False,
    )(g_pad, z_pad, rho, h, mask, m_g, v_g, a)


@dataclasses.dataclass(frozen=True)
class POFLConfig:
    """Hyper-parameters for the PO-FL simulator (defaults = paper Sec. V-A)."""

    n_devices: int = 30
    n_scheduled: int = 10
    alpha: float = 0.1
    policy: str = "pofl"
    # "without_replacement" (the paper's sequential Eq. 36 scan), "topk"
    # (Gumbel top-k draw — same law, different PRNG stream, no S-step scan),
    # or "bernoulli" (PO-FL-B Horvitz–Thompson variant)
    sampler: str = "without_replacement"
    tx_power: float = 1.0
    noise_power: float = 1e-11
    batch_size: int = 10
    lr0: float = 0.1
    lr_decay: float = 0.95
    lr_min: float = 1e-5
    simulate_physical: bool = False  # full Eq.5→8 path vs Eq.16 (same in law)
    backend: str = "jnp"  # AggregationBackend of the aggregation stage
    # -- local-update algorithm axis (core.local_update) ----------------
    # The defaults are legacy-equivalent: fedavg at one local step traces
    # the EXACT historical one-gradient round (bit-identical trajectories).
    local_algorithm: str = "fedavg"  # ALGORITHMS name (or the lattice's sentinel)
    local_steps: int = 1             # K local SGD steps per device per round
    local_lr: float | None = None    # local step size η_l; None → cfg.lr(t)
    fedprox_mu: float = 0.0          # FedProx proximal coefficient μ
    feddyn_alpha: float = 0.1        # FedDyn dynamic-regularizer coefficient
    # -- non-finite quarantine (sim.resilience) -------------------------
    # "propagate" (default): NaN/Inf aggregates flow through untouched — the
    # seed's exact program, zero new ops. "skip": a per-round finite-ness
    # guard quarantines any round whose aggregate ŷ^t contains a non-finite
    # entry (params and AlgState hold their previous values via lax.cond)
    # and counts it on the RoundMetrics.health subtree.
    on_nonfinite: str = "propagate"
    seed: int = 0

    def lr(self, t: jnp.ndarray) -> jnp.ndarray:
        """Paper Sec. V-A: η^t = max(η0 · 0.95^t, 1e-5)."""
        return jnp.maximum(self.lr0 * self.lr_decay**t, self.lr_min)


class DeviceData(NamedTuple):
    """Stacked per-device datasets.

    Equal shards (the paper's setting): ``features`` is ``(N, m, ...)`` and
    ``n_samples`` is None. Heterogeneous shards (e.g. Dirichlet-sized
    partitions): every shard is padded to a common ``m_max`` and
    ``n_samples[i] ≤ m_max`` marks device i's valid prefix — padded rows are
    never sampled, and the m_i/M fractions in the scheduling/weight math
    follow the true counts. ``features`` may be flat ``(N, m, d)`` vectors or
    image-shaped ``(N, m, H, W, C)`` batches (the model tasks' CNN case) —
    every stage treats the trailing dims opaquely. Eval-side padded test
    sets follow the same valid-prefix contract via
    ``repro.sim.tasks.TaskEval`` / ``models.small.make_eval_fn(n_valid=...)``.
    """

    features: jnp.ndarray  # (N, m_max, ...)
    labels: jnp.ndarray    # (N, m_max)
    n_samples: Any = None  # (N,) int valid-prefix lengths, or None (equal)

    @property
    def n_devices(self) -> int:
        return self.features.shape[0]

    @property
    def samples_per_device(self) -> int:
        """Padded (maximum) shard length m_max."""
        return self.features.shape[1]

    @property
    def data_frac(self) -> jnp.ndarray:
        """m_i / M — uniform for equal shards, true fractions otherwise."""
        n = self.features.shape[0]
        if self.n_samples is None:
            return jnp.full((n,), 1.0 / n)
        ns = jnp.asarray(self.n_samples, jnp.float32)
        return ns / jnp.sum(ns)


class History(NamedTuple):
    """Host-side metric record of the ``run_pofl`` driver.

    ``loss``/``test_acc`` come from the caller's ``eval_fn`` — any Python
    ``params -> (loss, acc)`` callable, including a model task's
    ``repro.sim.tasks.TaskEval`` (whose pad-masked eval counts only the true
    test rows of a padded set). The richer on-device record schema — the
    per-round ``RoundRecord`` with its optional ``diag``/``eval`` subtrees —
    lives in ``repro.sim.engine``; this NamedTuple is the stable legacy
    surface and its fields are append-only.
    """

    loss: list
    e_com: list
    e_var: list
    test_acc: list
    test_round: list


# --------------------------------------------------------------------------
# the round pipeline stages
# --------------------------------------------------------------------------
# Step 2 — the local stage — lives in ``core.local_update``:
# ``local_gradient_stage`` (the legacy single gradient, re-exported above)
# and ``local_update_stage`` (multi-step deltas under the algorithm axis).


def scheduling_stage(
    cfg: POFLConfig,
    stats: aircomp.GradStats,
    h_abs: jnp.ndarray,
    data_frac: jnp.ndarray,
    dim: int,
    alpha,
    noise_power,
    k_sched: jax.Array,
    avail: jnp.ndarray | None = None,
    policy_id: jnp.ndarray | None = None,
    return_probs: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Step 4: p_i^t (Eq. 34/Remark 2) → draw S^t → weights ρ (Eq. 37/HT).

    Returns ``(rho, mask)`` — per-device aggregation weights and the 0/1
    scheduled indicator — or ``(rho, mask, probs)`` when ``return_probs``
    (the obs diagnostics tap needs the scheduling distribution; the extra
    output changes no arithmetic on the default path). ``avail`` (sim
    dropout/churn) zeroes unavailable devices' probabilities before the
    draw.

    ``policy_id`` (a traced int32, ``scheduling.POLICY_IDS`` order) switches
    the stage to the FUSED dispatch the policy-vmapped lattice compiles: the
    probabilities come from ``scheduling_probs_by_id`` and the
    deterministic-policy weight rule is a value select instead of a Python
    branch. Per-cell values are bit-identical to the ``policy_id=None``
    string dispatch of the same policy — every branch's arithmetic is
    exactly the static version's, and both weight rules consume the same
    draw of the same ``k_sched``.
    """
    method = "topk" if cfg.sampler == "topk" else "sequential"
    if policy_id is None:
        probs = scheduling.scheduling_probs(
            cfg.policy, stats.norm, stats.var, h_abs, data_frac, dim,
            alpha, cfg.tx_power, noise_power,
        )
    else:
        probs = scheduling.scheduling_probs_by_id(
            policy_id, stats.norm, stats.var, h_abs, data_frac, dim,
            alpha, cfg.tx_power, noise_power,
        )
    if avail is not None:
        masked = probs * avail
        probs = safe_div(masked, jnp.sum(masked))

    if policy_id is None:
        if cfg.policy == "deterministic":
            sched = scheduling.sample_without_replacement(
                k_sched, probs, cfg.n_scheduled, method=method
            )
            rho = scheduling.deterministic_weights(sched, data_frac)
            mask = sched.mask
        elif cfg.sampler == "bernoulli":
            mask, pi = scheduling.sample_bernoulli(k_sched, probs, cfg.n_scheduled)
            rho = scheduling.bernoulli_weights(pi, data_frac)
        else:
            sched = scheduling.sample_without_replacement(
                k_sched, probs, cfg.n_scheduled, method=method
            )
            rho = scheduling.aggregation_weights(sched, probs, data_frac, cfg.n_scheduled)
            mask = sched.mask
        return (rho, mask, probs) if return_probs else (rho, mask)

    # fused dispatch: the policy is data, so the deterministic-vs-stochastic
    # weight rule is a select over values computed from the SAME draw (the
    # string path draws with the same key in either branch)
    is_det = policy_id == scheduling.DETERMINISTIC_ID
    sched = scheduling.sample_without_replacement(
        k_sched, probs, cfg.n_scheduled, method=method
    )
    rho_det = scheduling.deterministic_weights(sched, data_frac)
    if cfg.sampler == "bernoulli":
        mask_b, pi = scheduling.sample_bernoulli(k_sched, probs, cfg.n_scheduled)
        rho = jnp.where(is_det, rho_det, scheduling.bernoulli_weights(pi, data_frac))
        mask = jnp.where(is_det, sched.mask, mask_b)
    else:
        rho_seq = scheduling.aggregation_weights(
            sched, probs, data_frac, cfg.n_scheduled
        )
        rho = jnp.where(is_det, rho_det, rho_seq)
        mask = sched.mask
    return (rho, mask, probs) if return_probs else (rho, mask)


def lane_dense_layout(
    cfg: POFLConfig,
    params,
    model_shard: ModelShard | None = None,
    traced_algorithm: bool = False,
) -> grad_layout.Layout | None:
    """The round's lane-dense gradient layout (``core.grad_layout``), or
    None for the flat ``(N, D)`` block.

    Taken where the round keeps the block to itself — the static
    fedavg/fedprox one-gradient round, unsharded — and the aggregation runs
    the Pallas kernel, compiled on the TPU or interpreted when
    ``REPRO_PALLAS_INTERPRET`` asks for it. The jnp oracle (``auto`` on the
    CPU), AlgState, the local-step scan, the traced algorithm switch and the
    model-sharded route keep the flat block, op for op.
    """
    if (
        model_shard is not None
        or traced_algorithm
        or int(cfg.local_steps) != 1
        or cfg.local_algorithm not in STATELESS
        or AggregationBackend(cfg.backend) is not AggregationBackend.PALLAS_FUSED
    ):
        return None
    from repro.kernels.aircomp.ops import resolve_mode  # late: kernels↔core

    return grad_layout.plan(params) if resolve_mode("auto") else None


def _lane_dense_combine(
    layout: grad_layout.Layout,
    g: grad_layout.GradBlock,
    coeff: jnp.ndarray,
    m_g: jnp.ndarray,
    v_g: jnp.ndarray,
    a: jnp.ndarray,
    z: jnp.ndarray,
    use_pallas: str | bool,
) -> jnp.ndarray:
    """The fused Eq. 5→8 combine over a :class:`~repro.core.grad_layout.GradBlock`:
    the canonical noise draw mapped into the segments, the kernel over each
    segment, ŷ mapped back to canonical order. A flat remainder narrower
    than one lane tile (a bias) runs the kernel's jnp oracle — the same
    arithmetic, without a padded launch of its own."""
    from repro.kernels.aircomp import aircomp_aggregate_fused  # late: kernels↔core

    z_flat, z_dense = layout.segments(z)
    y_dense = tuple(
        aircomp_aggregate_fused(gs, coeff, m_g, v_g, a, zs, use_pallas=use_pallas)
        for gs, zs in zip(g.dense, z_dense)
    )
    y_flat = None
    if g.flat is not None:
        narrow = g.flat.shape[-1] < grad_layout.LANE
        y_flat = aircomp_aggregate_fused(
            g.flat, coeff, m_g, v_g, a, z_flat,
            use_pallas=False if narrow else use_pallas,
        )
    return layout.canonical(y_flat, y_dense)


def aggregation_stage(
    cfg: POFLConfig,
    g: jnp.ndarray,
    rho: jnp.ndarray,
    h: jnp.ndarray,
    mask: jnp.ndarray,
    k_noise: jax.Array,
    noise_power,
    use_pallas: str | bool = "auto",
    model_shard: ModelShard | None = None,
    stats: aircomp.GradStats | None = None,
    dim: int | None = None,
    layout: grad_layout.Layout | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Steps 5: transmit + AirComp aggregate per ``cfg.backend`` → (ŷ, e_com).

    ``jnp`` runs the exact reference chain; ``pallas_fused`` collapses the
    Eq. 5 normalize → Lemma-1 transmit scale → Eq. 7 superpose → Eq. 8
    denoise/denormalize into one pass over the gradient matrix
    (``kernels/aircomp``). Under the lattice's cell vmap the fused
    ``pallas_call`` batches into the trial-batched grid — exactly the
    ``aircomp_fused_batch`` program — without host-side dispatch.

    ``model_shard`` switches to the D-sharded route: ``g`` is then the
    padded block (``ModelShard.pad_features``), ``stats`` the psum'd
    statistics, ``dim`` the TRUE (unpadded) flat dimension — the noise draw
    stays the full-D draw of the same key (identical values to the
    unsharded path; only its placement is sharded) and the returned ŷ is
    still padded (slice ``[:dim]`` at the caller). ``e_com``'s closed form
    always uses the true ``dim``.

    ``layout`` (from :func:`lane_dense_layout`: ``pallas_fused``,
    unsharded) takes ``g`` as that layout's
    :class:`~repro.core.grad_layout.GradBlock` with its precomputed
    ``stats``; ŷ comes back in canonical order.
    """
    backend = AggregationBackend(cfg.backend)
    if model_shard is None:
        if backend is AggregationBackend.JNP:
            return aircomp.aircomp_aggregate(
                g, rho, h, mask, k_noise, cfg.tx_power, noise_power,
                simulate_physical=cfg.simulate_physical,
            )

        from repro.kernels.aircomp import aircomp_aggregate_fused  # late: kernels↔core

        if layout is None:
            stats = aircomp.local_stats(g)
            dim = g.shape[-1]
        else:
            dim = layout.dim
        m_g, v_g = aircomp.global_stats(stats, rho, mask)
        h_abs = jnp.abs(h)
        a = aircomp.denoise_scalar(rho, h_abs, mask, cfg.tx_power)
        z = jax.random.normal(k_noise, (dim,)) * jnp.sqrt(noise_power)
        coeff = mask * rho  # b_i h_i = ρ_i a exactly (Lemma-1 channel inversion)
        if layout is None:
            y_hat = aircomp_aggregate_fused(
                g, coeff, m_g, v_g, a, z, use_pallas=use_pallas
            )
        else:
            y_hat = _lane_dense_combine(layout, g, coeff, m_g, v_g, a, z, use_pallas)
        e_com = aircomp.distortion_closed_form(
            v_g, rho, h_abs, mask, dim, cfg.tx_power, noise_power
        )
        return y_hat, e_com

    if stats is None or dim is None:
        raise ValueError("model-sharded aggregation needs precomputed stats + dim")
    m_g, v_g = aircomp.global_stats(stats, rho, mask)
    h_abs = jnp.abs(h)
    a = aircomp.denoise_scalar(rho, h_abs, mask, cfg.tx_power)
    # same draw, same key, same values as the unsharded path — only the
    # padding tail (zeros) and the placement differ
    z = jax.random.normal(k_noise, (dim,)) * jnp.sqrt(noise_power)
    d_pad = g.shape[-1]
    if d_pad != dim:
        z = jnp.pad(z, (0, d_pad - dim))
    y_hat = _model_sharded_combine(
        cfg, model_shard, g, rho, h, mask, z, m_g, v_g, a, use_pallas
    )
    e_com = aircomp.distortion_closed_form(
        v_g, rho, h_abs, mask, dim, cfg.tx_power, noise_power
    )
    return y_hat, e_com


def apply_update_stage(
    cfg: POFLConfig, params, y_hat: jnp.ndarray, t,
    model_shard: ModelShard | None = None,
):
    """Step 6: w^{t+1} = w^t − η^t ŷ^t (flat update, re-raveled).

    Under a :class:`ModelShard` each updated leaf is constrained back to its
    model-sharded placement (``P(None, "model")`` on the last eligible dim)
    so the scan carry keeps a stable sharding across rounds instead of
    drifting to whatever layout the flat update left behind.
    """
    flat_params, unravel_p = ravel_pytree(params)
    new_params = unravel_p(flat_params - cfg.lr(t) * y_hat)
    if model_shard is not None:
        new_params = jax.tree.map(
            lambda leaf: jax.lax.with_sharding_constraint(
                leaf, model_shard.leaf_sharding(np.shape(leaf))
            ),
            new_params,
        )
    return new_params


# --------------------------------------------------------------------------
# the composed round
# --------------------------------------------------------------------------


def round_algorithm(
    loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    data: DeviceData,
    cfg: POFLConfig,
    params,
    h: jnp.ndarray,
    k_batch: jax.Array,
    k_sched: jax.Array,
    k_noise: jax.Array,
    t: jnp.ndarray,
    noise_power: jnp.ndarray | float | None = None,
    alpha: jnp.ndarray | float | None = None,
    avail: jnp.ndarray | None = None,
    policy_id: jnp.ndarray | None = None,
    diagnostics: bool = False,
    model_shard: ModelShard | None = None,
    alg_state: AlgState | None = None,
    algorithm_id: jnp.ndarray | None = None,
    fault_round: jnp.ndarray | None = None,
) -> tuple[Any, AlgState | None, RoundMetrics]:
    """Steps 2–6 of Algorithm 1 for one round, given this round's channel ``h``.

    Returns ``(new_params, new_alg_state, metrics)``. ``alg_state`` is the
    per-device local-algorithm state (:class:`~repro.core.local_update.AlgState`
    in the engine's scan carry; ``None`` — the default and the only value the
    legacy path ever passes — flattens to an empty subtree and is returned
    unchanged). ``algorithm_id`` (traced int32, ``local_update.ALGORITHM_IDS``
    order) switches the local stage to the fused ``lax.switch`` dispatch the
    multi-algorithm lattice compiles; ``None`` keeps the static
    ``cfg.local_algorithm`` string dispatch — and the default
    ``fedavg``/``local_steps=1`` config traces the EXACT legacy program.

    Composes the four pipeline stages. ``noise_power`` / ``alpha`` default to
    the (static) config values but may be traced arrays — the simulation
    lattice vmaps over them. Everything structural (sampler, |S|, batch
    size, backend) stays static. The POLICY is static by default
    (``cfg.policy`` string dispatch) but becomes one more traced leaf when
    ``policy_id`` is given (``scheduling.POLICY_IDS`` order): the fused
    lattice vmaps over it, so every policy of a sweep shares ONE compiled
    program. Per-cell values are bit-identical between the two dispatches.

    ``avail`` is an optional (N,) 0/1 availability mask (sim dropout/churn
    scenarios): unavailable devices get zero scheduling probability this
    round. ``None`` (the default, and the only value the legacy path ever
    passes) skips the masking entirely, keeping the static-scenario
    trajectory bit-identical to the seed implementation.

    ``diagnostics`` (static, driven by ``ObsConfig.diagnostics``) adds the
    cheap per-round taps of :class:`repro.core.metrics.RoundDiagnostics` to
    the returned metrics. Off — the default — the traced program is
    bit-identical to the seed: no extra ops, ``metrics.diag is None``.

    ``model_shard`` (a :class:`ModelShard`, from an engine whose mesh has a
    ``"model"`` axis > 1) reroutes the D-elementwise hot path — stats,
    aggregation, params carry — through model-sharded ``shard_map`` blocks;
    ``None`` keeps the unsharded trace exactly.

    ``fault_round`` (traced int32 scalar, or ``None`` — the default and the
    only value every pre-existing path passes) is the deterministic
    fault-injection hook of ``repro.sim.resilience``: when the current round
    ``t`` equals it, the aggregate ŷ^t is poisoned to NaN *as a value select*
    — the fault point is input data, not trace structure, so a lattice with
    one poisoned cell runs the SAME compiled program as an unpoisoned one
    (``fault_round = -1`` never fires) and every other cell's lanes are
    bitwise unchanged. ``cfg.on_nonfinite`` decides what happens next:
    ``"propagate"`` (default) lets the NaN flow — the seed's exact program
    when ``fault_round`` is also None — while ``"skip"`` quarantines any
    non-finite aggregate (injected or organic): ``new_params`` and the
    AlgState hold their previous values via ``lax.cond`` and the round is
    counted on the returned :class:`~repro.core.metrics.RoundHealth` subtree
    (``metrics.health``; ``None`` under "propagate" — the empty-subtree
    trick, fourth application).
    """
    noise_power = cfg.noise_power if noise_power is None else noise_power
    alpha = cfg.alpha if alpha is None else alpha

    data_frac = data.data_frac

    with jax.named_scope("aggregation"):
        if policy_id is None:
            noise_free = cfg.policy == "noisefree"
            agg_noise_power = 0.0 if noise_free else noise_power
        else:
            # traced policy: σ_z² = 0 for noisefree cells is a runtime select —
            # sqrt(0)·z and the 0-noise closed forms are exact, so values match
            # the static 0.0 of the string path bit for bit
            agg_noise_power = jnp.where(
                policy_id == scheduling.NOISEFREE_ID, 0.0, noise_power
            )

    # the lane-dense carry (core.grad_layout) where the kernel aggregates
    # the one-gradient round; None keeps the flat (N, D) block
    layout = lane_dense_layout(
        cfg, params, model_shard, traced_algorithm=algorithm_id is not None
    )

    # -- step 2: local updates (K SGD steps per device → delta) -------
    with jax.named_scope("local_update"):
        alg_state_in = alg_state  # pre-round state (the quarantine hold value)
        if layout is not None:
            # stateless one-gradient round: alg_state passes through
            g = local_gradient_block(loss_fn, data, cfg, params, k_batch, layout)
            dim = layout.dim
        else:
            g, alg_state = local_update_stage(
                loss_fn, data, cfg, params, k_batch, t,
                alg_state=alg_state, algorithm_id=algorithm_id,
            )  # (N, D) — the legacy single gradient when fedavg/local_steps=1
            dim = g.shape[-1]

    # -- step 3: uploaded scalar statistics ---------------------------
    with jax.named_scope("statistics"):
        if model_shard is not None:
            # pad D to |model|·tile_d, place P(None, "model"); stats become
            # masked shard-local reductions + small psums over the model axis
            g = model_shard.pad_features(g, dim)
            stats = _model_sharded_local_stats(model_shard, g, dim)
        elif layout is not None:
            stats = grad_layout.block_stats(g, dim)
        else:
            stats = aircomp.local_stats(g)

    # -- step 4: scheduling -------------------------------------------
    with jax.named_scope("scheduling"):
        h_abs = jnp.abs(h)
        sched_out = scheduling_stage(
            cfg, stats, h_abs, data_frac, dim, alpha, noise_power, k_sched,
            avail=avail, policy_id=policy_id, return_probs=diagnostics,
        )
        rho, mask = sched_out[0], sched_out[1]

    # -- steps 5-6: AirComp aggregation + model update ----------------
    with jax.named_scope("aggregation"):
        y_hat, e_com = aggregation_stage(
            cfg, g, rho, h, mask, k_noise, agg_noise_power,
            model_shard=model_shard, stats=stats, dim=dim, layout=layout,
        )
        if model_shard is not None:
            # ŷ comes back padded (its tail is sqrt(V_g)/a·0 + M_g, not zero) —
            # slice to the true D before the update and the norm tap
            y_hat = y_hat[:dim]
        if fault_round is not None:
            # deterministic NaN injection: a value select on the traced fault
            # point, so the no-fault program (fault_round = -1) is the same
            # executable and every unpoisoned lane is bitwise unchanged
            y_hat = jnp.where(
                t == jnp.asarray(fault_round, jnp.float32),
                jnp.full_like(y_hat, jnp.nan),
                y_hat,
            )
    with jax.named_scope("round_record"):
        if layout is not None:
            e_var = grad_layout.block_update_variance(g, rho, mask, data_frac)
        else:
            # e_var on the padded g is exact: padded columns are zero in every term
            e_var = scheduling.global_update_variance(
                g, rho, mask, data_frac, cfg.n_scheduled
            )

    with jax.named_scope("apply_update"):
        new_params = apply_update_stage(cfg, params, y_hat, t, model_shard=model_shard)
        health = None
        if cfg.on_nonfinite == "skip":
            # quarantine: a non-finite aggregate (injected or organic) must not
            # poison the carry — hold BOTH the params and the local-algorithm
            # state, i.e. the round never happened for the model. The PRNG chain
            # (engine carry) is untouched either way, so quarantined sweeps stay
            # deterministic.
            finite = jnp.all(jnp.isfinite(y_hat))
            new_params, alg_state = jax.lax.cond(
                finite,
                lambda upd, _prev: upd,
                lambda _upd, prev: prev,
                (new_params, alg_state),
                (params, alg_state_in),
            )
            health = RoundHealth(
                nonfinite=(~finite).astype(jnp.float32)
            )
        elif cfg.on_nonfinite != "propagate":
            raise ValueError(
                f"POFLConfig.on_nonfinite must be 'propagate' or 'skip', "
                f"got {cfg.on_nonfinite!r}"
            )

    with jax.named_scope("round_record"):
        a = aircomp.denoise_scalar(rho, h_abs, mask, cfg.tx_power)
        diag = None
        if diagnostics:
            _, v_g = aircomp.global_stats(stats, rho, mask)
            diag = diagnostics_taps(
                sched_out[2], stats.norm, v_g, a, h_abs, cfg.tx_power,
                agg_noise_power,
            )
        metrics = RoundMetrics(
            loss=jnp.zeros(()),  # filled by caller's eval if desired
            e_com=e_com,
            e_var=e_var,
            grad_norm=jnp.linalg.norm(y_hat),
            n_scheduled=jnp.sum(mask),
            a_scalar=a,
            diag=diag,
            health=health,
        )
    return new_params, alg_state, metrics


def make_round_step(
    loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    data: DeviceData,
    channel: ChannelState,
    cfg: POFLConfig,
):
    """Build the jitted single-round step implementing Algorithm 1."""

    def round_step(params, key, t):
        k_batch, k_chan, k_sched, k_noise = jax.random.split(key, 4)
        h = channel.sample(k_chan)
        new_params, _, m = round_algorithm(
            loss_fn, data, cfg, params, h, k_batch, k_sched, k_noise, t
        )
        return new_params, m

    return jax.jit(round_step)


def run_pofl(
    loss_fn,
    params0,
    data: DeviceData,
    cfg: POFLConfig,
    n_rounds: int,
    eval_fn: Callable[[Any], tuple[float, float]] | None = None,
    eval_every: int = 5,
    channel_cfg: ChannelConfig | None = None,
) -> tuple[Any, History]:
    """Run Algorithm 1 for ``n_rounds`` and return (params, history).

    Compatibility wrapper over ``repro.sim.engine.SimEngine``: the T-round
    loop is a single-static-length active-mask ``lax.scan`` chunked at the
    evaluation boundaries, so metrics only sync to host once per eval
    interval instead of once per round. The trajectory is identical (same
    PRNG key discipline, same round body) to the historical per-round Python
    loop — see tests/test_sim.py.

    Engines (and their jitted scans) are cached across calls keyed by
    ``(task, cfg-minus-seed, backend)`` — a repeat call with the same config
    (any seed) reuses the compiled program with zero new traces
    (``repro.sim.engine.engine_cache_stats``).
    """
    from repro.sim.engine import cached_engine  # late import: sim builds on core

    engine = cached_engine(loss_fn, data, cfg, channel_cfg=channel_cfg)
    return engine.run_with_history(
        params0, n_rounds, eval_fn=eval_fn, eval_every=eval_every,
        seed=cfg.seed,
    )
