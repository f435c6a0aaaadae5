"""Local-update algorithms: multi-step device optimization behind one axis.

The seed round body uploads a single mini-batch gradient per device per
round. Real over-the-air FL systems upload multi-step local-update DELTAS,
and the standard remedies for non-IID client drift (FedProx, FedDyn,
SCAFFOLD) differ only in the *effective gradient* each local SGD step
follows. This module factors that into one stage:

    local_update_stage: (params, k_batch, alg_state) -> (Δ, alg_state')

Each device runs ``cfg.local_steps`` SGD steps (an inner ``lax.scan`` over
per-step mini-batch keys) on its own copy of the weights and uploads the
*average effective gradient*

    Δ_i = (1/K) Σ_k ĝ_i(w_i^k)   ==   (w^t − w_i^K) / (K · η_l)

(the equalities are exact in exact arithmetic; the accumulated form keeps
``K=1`` literally a single gradient). Δ_i feeds the unchanged scheduling →
AirComp → apply-update chain, so Lemma 2's ``Δ_i/π_i`` reweighting — and the
whole unbiasedness analysis — transfers verbatim from gradients to deltas
(pinned by tests/test_local_update.py's hypothesis suite).

The algorithm axis mirrors PR 5's ``policy_id`` design exactly:

  * ``ALGORITHMS`` is an APPEND-ONLY tuple — ``ALGORITHM_IDS[name]`` is the
    int32 ``lax.switch`` branch index, so ids are stable forever (same
    contract as ``scheduling.POLICY_IDS``; see ROADMAP "builder notes").
  * Static dispatch (``algorithm_id=None``): ``cfg.local_algorithm`` selects
    the branch as a Python string; ``fedavg`` (or ``fedprox``, whose
    proximal term is identically zero on the first local step) at
    ``local_steps=1`` short-circuits to :func:`local_gradient_stage` — the
    EXACT legacy one-gradient ops, so every seed-pinned trajectory is
    bitwise unchanged.
  * Traced dispatch (``algorithm_id`` an int32 array): one ``lax.switch``
    branch table over the effective-gradient rules, so a multi-algorithm
    lattice compiles ONCE (``sim.lattice`` vmaps the id per cell).

Per-device algorithm state rides the engine's donated scan carry as
:class:`AlgState` — ``h`` is FedDyn's drift h_i, ``c`` is SCAFFOLD's control
variate c_i, and ``None`` leaves flatten to EMPTY pytree subtrees (the PR-6
``diag=None`` trick), so stateless algorithms leave the carry structure —
and therefore the compiled legacy program — untouched.

The effective-gradient rules (w0 = w^t broadcast per device):

    fedavg    ĝ = g(w)
    fedprox   ĝ = g(w) + μ (w − w0)                   [μ = cfg.fedprox_mu]
    feddyn    ĝ = g(w) − h_i + α_d (w − w0);  h_i' = h_i − α_d (w_i^K − w0)
    scaffold  ĝ = g(w) − c_i + c̄;            c_i' = c_i − c̄ + Δ_i
                                              (Option II, uniform c̄ = mean c_i)
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

# APPEND-ONLY (the lax.switch branch table below and every persisted
# algorithm id depend on these positions — add new algorithms at the END)
ALGORITHMS = ("fedavg", "fedprox", "feddyn", "scaffold")
ALGORITHM_IDS = {name: i for i, name in enumerate(ALGORITHMS)}
FEDAVG_ID = ALGORITHM_IDS["fedavg"]
FEDPROX_ID = ALGORITHM_IDS["fedprox"]
FEDDYN_ID = ALGORITHM_IDS["feddyn"]
SCAFFOLD_ID = ALGORITHM_IDS["scaffold"]

# algorithms whose per-device state is empty (AlgState leaves all None →
# the scan carry keeps the legacy pytree structure)
STATELESS = ("fedavg", "fedprox")


def algorithm_id(algorithm: str) -> int:
    """The stable ``lax.switch`` branch index of a local-update algorithm."""
    if algorithm not in ALGORITHM_IDS:
        raise ValueError(
            f"unknown local_algorithm {algorithm!r}; choose from {ALGORITHMS}"
        )
    return ALGORITHM_IDS[algorithm]


class AlgState(NamedTuple):
    """Per-device local-algorithm state carried across rounds.

    ``None`` fields flatten to EMPTY pytree subtrees (zero leaves, zero
    ops), so a stateless algorithm's :class:`~repro.sim.engine.SimState`
    is structurally identical to the pre-algorithm-axis carry.
    """

    h: Any = None  # FedDyn per-device drift h_i, (N, D) or None
    c: Any = None  # SCAFFOLD per-device control variate c_i, (N, D) or None


def init_state(
    local_algorithm: str, n_devices: int, dim: int, full: bool = False
) -> AlgState | None:
    """Zero-initialized algorithm state for one cell.

    ``full=True`` builds EVERY state field regardless of the algorithm name —
    the traced ``lax.switch`` dispatch evaluates all branches, so a fused
    multi-algorithm lattice must carry the union (fedavg/fedprox branches
    simply pass h/c through unchanged). ``full=False`` (static dispatch)
    returns ``None`` for stateless algorithms so the carry structure — and
    every pinned trajectory — stays bit-identical to the legacy engine.
    """
    zeros = lambda: jnp.zeros((n_devices, dim), jnp.float32)  # noqa: E731
    if full:
        return AlgState(h=zeros(), c=zeros())
    algorithm_id(local_algorithm)  # hard error on unknown names
    if local_algorithm == "feddyn":
        return AlgState(h=zeros(), c=None)
    if local_algorithm == "scaffold":
        return AlgState(h=None, c=zeros())
    return None


def draw_minibatch(data, cfg, k_batch: jax.Array):
    """Per-device mini-batch draw → (feats, labels), each leading (N, B).

    Equal shards keep the seed's exact ``randint`` draw (bit-identical
    trajectories); heterogeneous shards draw uniformly over each device's
    valid prefix so padded rows are never touched.
    """
    n = data.n_devices
    m = data.samples_per_device
    if data.n_samples is None:
        idx = jax.random.randint(k_batch, (n, cfg.batch_size), 0, m)
    else:
        # n_samples is static partition metadata — reject empty devices at
        # trace time (idx = min(·, -1) would wrap to the last PADDED row)
        if (np.asarray(data.n_samples) < 1).any():
            raise ValueError(
                "every device needs n_samples >= 1; drop empty devices from "
                "the partition instead"
            )
        ns = jnp.asarray(data.n_samples, jnp.int32)
        u = jax.random.uniform(k_batch, (n, cfg.batch_size))
        idx = jnp.minimum(
            (u * ns[:, None].astype(u.dtype)).astype(jnp.int32), ns[:, None] - 1
        )
    feats = jnp.take_along_axis(
        data.features,
        idx.reshape((n, cfg.batch_size) + (1,) * (data.features.ndim - 2)),
        axis=1,
    )
    labels = jnp.take_along_axis(data.labels, idx, axis=1)
    return feats, labels


def _device_gradients(loss_fn, params, feats, labels):
    """vmap(jax.grad) over the device axis → stacked flat gradients (N, D)."""

    def one(fx, fy):
        g = jax.grad(loss_fn)(params, fx, fy)
        flat, _ = ravel_pytree(g)
        return flat

    return jax.vmap(one)(feats, labels)


def _device_gradients_at(loss_fn, unravel, w_flat, feats, labels):
    """Per-device gradients at per-device weights → (N, D). Unlike
    :func:`_device_gradients` the weights have diverged (local steps > 1),
    so the vmap carries a flat weight row per device."""

    def one(wf, fx, fy):
        g = jax.grad(loss_fn)(unravel(wf), fx, fy)
        flat, _ = ravel_pytree(g)
        return flat

    return jax.vmap(one)(w_flat, feats, labels)


def local_gradient_stage(
    loss_fn: Callable,
    data,
    cfg,
    params,
    k_batch: jax.Array,
) -> jnp.ndarray:
    """Step 2 of Algorithm 1: one mini-batch draw + vmapped grads → (N, D).

    The legacy one-gradient round body — kept verbatim as the ``fedavg`` /
    ``local_steps=1`` short-circuit of :func:`local_update_stage`, so every
    seed-pinned trajectory stays bitwise unchanged.
    """
    feats, labels = draw_minibatch(data, cfg, k_batch)
    return _device_gradients(loss_fn, params, feats, labels)


def local_gradient_block(
    loss_fn: Callable,
    data,
    cfg,
    params,
    k_batch: jax.Array,
    layout,
):
    """:func:`local_gradient_stage` with the per-device gradients left in
    ``layout``'s segments (a ``core.grad_layout.GradBlock``) instead of
    raveled: the same draw and the same gradients, only not relaid out."""
    feats, labels = draw_minibatch(data, cfg, k_batch)
    grads = jax.vmap(lambda fx, fy: jax.grad(loss_fn)(params, fx, fy))(feats, labels)
    return layout.block(grads)


def _effective_gradient_branches(mu, a_dyn, h, c, cbar):
    """The APPEND-ONLY ``lax.switch`` branch table, ``ALGORITHMS`` order.

    Every branch maps ``(g, drift)`` — the stacked mini-batch gradients and
    ``w − w0`` per device — to the effective gradient its local SGD step
    follows. New algorithms append; existing indices never move (same
    contract as ``scheduling.scheduling_probs_by_id``).
    """
    return [
        lambda g, drift: g,                      # fedavg
        lambda g, drift: g + mu * drift,         # fedprox (proximal pull)
        lambda g, drift: g - h + a_dyn * drift,  # feddyn (dynamic regularizer)
        lambda g, drift: g - c + cbar,           # scaffold (control variates)
    ]


def local_update_stage(
    loss_fn: Callable,
    data,
    cfg,
    params,
    k_batch: jax.Array,
    t,
    alg_state: AlgState | None = None,
    algorithm_id: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, AlgState | None]:
    """Steps 2–2b: ``cfg.local_steps`` local SGD steps per device → (Δ, state').

    Returns the (N, D) per-device average effective gradient Δ_i — a drop-in
    replacement for the legacy single gradient in the scheduling/AirComp
    chain — plus the updated :class:`AlgState`.

    Dispatch contract (mirrors ``core.pofl.scheduling_stage``):

      * ``algorithm_id=None`` → static string dispatch on
        ``cfg.local_algorithm``. ``fedavg``/``fedprox`` at ``local_steps=1``
        short-circuit to the EXACT legacy :func:`local_gradient_stage` ops
        (the proximal term is identically zero on the first local step) —
        the bit-identity pin every golden trajectory rides on.
      * ``algorithm_id`` a traced int32 (``ALGORITHM_IDS`` order) → the
        ``lax.switch`` branch table; the fused lattice vmaps it per cell,
        and ``alg_state`` must then carry EVERY field
        (``init_state(..., full=True)``) because all branches are traced.

    The per-step mini-batch keys split off ``k_batch`` — except at
    ``local_steps=1``, where the single step consumes ``k_batch`` itself so
    the draw (and the whole round) matches the legacy program bit for bit.
    """
    K = int(cfg.local_steps)
    if K < 1:
        raise ValueError(f"local_steps must be >= 1, got {K}")
    if algorithm_id is None:
        name = cfg.local_algorithm
        if name not in ALGORITHM_IDS:
            raise ValueError(
                f"unknown local_algorithm {name!r}; choose from {ALGORITHMS}"
            )
        if K == 1 and name in STATELESS:
            # op-for-op the legacy one-gradient round (Δ_i = g_i exactly)
            return local_gradient_stage(loss_fn, data, cfg, params, k_batch), alg_state
        if name not in STATELESS and (
            alg_state is None or getattr(alg_state, "h" if name == "feddyn" else "c") is None
        ):
            raise ValueError(
                f"{name} needs per-device AlgState in the scan carry; run it "
                "through repro.sim.SimEngine (init_state builds the state)"
            )
    else:
        name = None
        if alg_state is None or alg_state.h is None or alg_state.c is None:
            raise ValueError(
                "traced algorithm dispatch evaluates every branch, so "
                "alg_state must carry all fields — init_state(..., full=True)"
            )

    flat0, unravel = ravel_pytree(params)
    n = data.n_devices
    w0 = jnp.broadcast_to(flat0, (n, flat0.size))
    lr_l = cfg.lr(t) if cfg.local_lr is None else jnp.asarray(cfg.local_lr, jnp.float32)
    mu = jnp.asarray(cfg.fedprox_mu, jnp.float32)
    a_dyn = jnp.asarray(cfg.feddyn_alpha, jnp.float32)

    h = None if alg_state is None else alg_state.h
    c = None if alg_state is None else alg_state.c
    cbar = None if c is None else jnp.mean(c, axis=0)

    if algorithm_id is None:
        eff = _effective_gradient_branches(mu, a_dyn, h, c, cbar)[ALGORITHM_IDS[name]]
    else:
        branches = _effective_gradient_branches(mu, a_dyn, h, c, cbar)
        alg_id = algorithm_id

        def eff(g, drift):
            return jax.lax.switch(alg_id, branches, g, drift)

    # K=1 consumes k_batch itself (the legacy draw); K>1 splits per step
    step_keys = k_batch[None] if K == 1 else jax.random.split(k_batch, K)

    def step(carry, k_step):
        w, acc = carry
        feats, labels = draw_minibatch(data, cfg, k_step)
        g = _device_gradients_at(loss_fn, unravel, w, feats, labels)
        ghat = eff(g, w - w0)
        return (w - lr_l * ghat, acc + ghat), None

    (w_k, acc), _ = jax.lax.scan(step, (w0, jnp.zeros_like(w0)), step_keys)
    delta = acc / K                    # (w0 − w_K) / (K η_l) in exact arithmetic
    drift_k = w_k - w0                 # per-device end-of-round drift

    if algorithm_id is None:
        if name == "feddyn":
            new_state = AlgState(h=h - a_dyn * drift_k, c=None)
        elif name == "scaffold":
            new_state = AlgState(h=None, c=c - cbar + delta)
        else:
            new_state = alg_state
    else:
        # state updates switch on the same branch index (ALGORITHMS order,
        # append-only): stateless branches pass (h, c) through unchanged
        new_h, new_c = jax.lax.switch(
            algorithm_id,
            [
                lambda: (h, c),                          # fedavg
                lambda: (h, c),                          # fedprox
                lambda: (h - a_dyn * drift_k, c),        # feddyn
                lambda: (h, c - cbar + delta),           # scaffold
            ],
        )
        new_state = AlgState(h=new_h, c=new_c)
    return delta, new_state
