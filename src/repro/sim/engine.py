"""The scanned PO-FL round engine.

Runs Algorithm 1 (``core.pofl.round_algorithm``) under ``lax.scan`` with the
whole carry — params, PRNG key, channel-process state — resident on device,
so a T-round segment is ONE dispatch with no per-round host sync. The carry
is donated on accelerator backends (the previous round's buffers are reused
in place).

Key discipline is bit-identical to the historical per-round ``run_pofl``
Python loop (pinned by tests/test_sim.py):

    key = PRNGKey(cfg.seed)
    k_chan_init, key = split(key)           # channel process init
    per round: key, k_round = split(key)
               k_batch, k_chan, k_sched, k_noise = split(k_round, 4)

Three entry points:

  * :meth:`SimEngine.init` — build the initial :class:`SimState` (pure; the
    seed may be a traced scalar, so lattice cells vmap over it).
  * :meth:`SimEngine.scan_rounds` — the pure scanned program
    ``(state, t_ints, do_eval, noise_power, alpha) -> (state, RoundRecord)``;
    ``repro.sim.lattice`` vmaps this across cells. ``noise_power``/``alpha``
    may be traced (lattice axes); anything structural is static.
  * :meth:`SimEngine.run_with_history` — the ``run_pofl``-compatible driver:
    a single-STATIC-length active-mask scan per segment between eval rounds
    (inactive tail rounds are ``lax.cond`` no-ops that touch neither the
    PRNG chain nor the carry), evaluate with an arbitrary Python ``eval_fn``
    on the host, return ``(params, History)``. One trace per (engine,
    segment length) — not per distinct chunk length.

Engines themselves are cached across ``run_pofl`` calls by
:func:`cached_engine`, keyed by (task identity, cfg-minus-seed — which
includes the aggregation backend — channel config, scenario): a repeat call
with the same config reuses both the engine object and every jit trace it
has accumulated (:func:`engine_cache_stats` exposes hit/miss counters).
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import OrderedDict
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import local_update
from repro.core.channel import ChannelConfig
from repro.core.metrics import RoundDiagnostics, zero_round_health
from repro.core.pofl import (
    DeviceData, History, ModelShard, POFLConfig, lane_dense_layout,
    round_algorithm,
)
from repro.obs.config import DEFAULT_OBS, ObsConfig
from repro.obs.registry import counter_add, gauge_set, metric_value, reset_metrics
from repro.obs.spans import span
from repro.obs.stages import register_module
from repro.sim.compile_cache import install_listener
from repro.sim.scenario import make_channel_process
from repro.sim.tasks import TaskEval, zero_eval_record

# per-engine cap on cached AOT lattice executables (LRU eviction)
_LATTICE_EXECUTABLES_MAX = 8

# The cfg.policy sentinel of a POLICY-FUSED engine (``repro.sim.lattice``
# with ``fuse_policies=True``): the policy is a traced per-cell input
# (``policy_id``), so the engine's static policy string is deliberately not
# a real policy — it only keys the engine cache, making the whole
# multi-policy lattice ONE cache entry (and one compile).
FUSED_POLICY = "__fused__"

# The cfg.local_algorithm sentinel of an ALGORITHM-FUSED engine
# (``repro.sim.lattice`` with a multi-algorithm ``LatticeSpec``): the
# algorithm is a traced per-cell input (``algorithm_id``), so the engine's
# static algorithm string is deliberately not a real algorithm — it only
# keys the engine cache, making the whole multi-algorithm lattice ONE cache
# entry (and one compile). Same design as :data:`FUSED_POLICY`.
FUSED_ALGORITHM = "__fused__"


class SimState(NamedTuple):
    """The donated scan carry: everything that evolves across rounds.

    ``alg`` is the per-device local-algorithm state
    (:class:`~repro.core.local_update.AlgState` — FedDyn h_i / SCAFFOLD c_i);
    its default ``None`` flattens to an EMPTY pytree subtree, so stateless
    algorithms (the legacy fedavg path included) keep the carry structure —
    and every pinned trajectory — bit-identical to the pre-algorithm-axis
    engine (the PR-6 ``diag=None`` trick).
    """

    params: Any       # model pytree
    key: jax.Array    # PRNG chain
    chan: Any         # channel-process state pytree
    alg: Any = None   # local-algorithm state (AlgState), or None (stateless)


class RoundRecord(NamedTuple):
    """Per-round on-device metric record (stacked over rounds by the scan).

    ``diag`` is the :class:`~repro.core.metrics.RoundDiagnostics` subtree
    when the engine's :class:`~repro.obs.config.ObsConfig` asks for
    diagnostics, else ``None`` — which flattens to an EMPTY pytree subtree,
    so the off-path record has exactly the seed's leaves (pinned
    trajectories, ``launch.distributed`` serialization, and the gather
    programs all see an unchanged structure).

    ``eval`` applies the same trick to the model-task eval curves
    (``repro.sim.tasks``): it is the structured
    :class:`~repro.sim.tasks.EvalRecord` when the engine's ``eval_fn`` is a
    :class:`~repro.sim.tasks.TaskEval`, else ``None`` (OFF by default) —
    legacy tuple eval_fns and eval-less runs keep the seed's exact record
    pytree, so every pre-existing pinned trajectory stays bitwise unchanged.

    ``health`` is the fourth application of the same trick: the
    :class:`~repro.core.metrics.RoundHealth` non-finite quarantine counters
    when ``POFLConfig.on_nonfinite="skip"``, else ``None`` — the default
    "propagate" keeps the seed's exact record pytree and zero new ops.
    """

    e_com: jnp.ndarray        # Eq. 15 closed-form communication distortion
    e_var: jnp.ndarray        # realized global update variance (Thm. 1)
    grad_norm: jnp.ndarray    # ||ŷ^t||
    n_scheduled: jnp.ndarray  # realized |S^t|
    loss: jnp.ndarray         # eval loss (0 where not evaluated)
    acc: jnp.ndarray          # eval accuracy (0 where not evaluated)
    diag: Any = None          # RoundDiagnostics taps, or None (default)
    eval: Any = None          # tasks.EvalRecord subtree, or None (default)
    health: Any = None        # RoundHealth quarantine taps, or None (default)


# the always-present scalar record fields (diag/eval are optional subtrees)
_RECORD_SCALARS = ("e_com", "e_var", "grad_norm", "n_scheduled", "loss", "acc")


def _zero_record(
    diagnostics: bool = False, task_eval: bool = False, health: bool = False
) -> RoundRecord:
    """A zero record matching the engine's record pytree (the inactive
    ``lax.cond`` branch must mirror ``round_body``'s structure exactly)."""
    scalars = [jnp.zeros((), jnp.float32) for _ in _RECORD_SCALARS]
    diag = None
    if diagnostics:
        diag = RoundDiagnostics(
            *(jnp.zeros((), jnp.float32) for _ in RoundDiagnostics._fields)
        )
    return RoundRecord(
        *scalars, diag=diag, eval=zero_eval_record() if task_eval else None,
        health=zero_round_health() if health else None,
    )


def _default_channel_cfg(cfg: POFLConfig) -> ChannelConfig:
    return ChannelConfig(
        n_devices=cfg.n_devices,
        tx_power=cfg.tx_power,
        noise_power=cfg.noise_power,
    )


class SimEngine:
    """Scan-over-rounds engine for one (task, config, channel scenario).

    Args:
      loss_fn: per-device loss ``f(params, x, y)`` (jax-traceable).
      data:    stacked per-device :class:`DeviceData` (equal shards or
        padded heterogeneous shards with ``n_samples``).
      cfg:     :class:`POFLConfig` (policy/sampler/|S|/batch/backend are
        static).
      channel_cfg: physical-layer constants; defaults to the config the
        historical ``run_pofl`` built from ``cfg``.
      scenario: channel-process name from ``sim.scenario.CHANNEL_SCENARIOS``.
      scenario_params: extra kwargs for the scenario (e.g. ``corr=0.95``).
      eval_fn: optional *traceable* ``params -> (loss, acc)`` evaluated
        inside the scan on rounds flagged by ``do_eval`` (used by the
        lattice; ``run_with_history`` instead takes an arbitrary Python
        callable and evaluates between chunks).

    ``mesh`` (a ``jax.sharding.Mesh`` or None) is carried as engine identity:
    the engine itself never reads it — input placement decides where the
    lattice program runs — but meshed and unmeshed engines must not share
    trace counters or cache slots (see :func:`cached_engine`), so it keys
    both.

    ``n_traces`` counts how many times the chunked scan has been (re)traced —
    the CI retrace guard asserts it stays flat across repeat ``run_pofl``
    calls with the same config. ``n_lattice_traces`` is the same counter for
    the vmapped-cells lattice program (:meth:`run_lattice_cells`).
    """

    def __init__(
        self,
        loss_fn: Callable,
        data: DeviceData,
        cfg: POFLConfig,
        channel_cfg: ChannelConfig | None = None,
        scenario: str = "static_rayleigh",
        scenario_params: dict | None = None,
        eval_fn: Callable | None = None,
        mesh: Any | None = None,
        obs: ObsConfig | None = None,
    ):
        self.loss_fn = loss_fn
        self.data = data
        self.cfg = cfg
        self.channel_cfg = channel_cfg or _default_channel_cfg(cfg)
        self.process = make_channel_process(
            scenario, self.channel_cfg, **(scenario_params or {})
        )
        self.eval_fn = eval_fn
        # A TaskEval (repro.sim.tasks) upgrades the record pytree with the
        # structured ``eval`` subtree; any other eval_fn keeps it None (the
        # empty-subtree OFF default — pinned trajectories stay bitwise).
        self._task_eval = eval_fn if isinstance(eval_fn, TaskEval) else None
        self.mesh = mesh
        # hard error on unknown algorithm names at engine construction (the
        # FUSED_ALGORITHM sentinel is the lattice's cache-key marker: the
        # per-cell traced algorithm_id does the real dispatch)
        if cfg.local_algorithm != FUSED_ALGORITHM:
            local_update.algorithm_id(cfg.local_algorithm)
        if cfg.on_nonfinite not in ("propagate", "skip"):
            raise ValueError(
                "POFLConfig.on_nonfinite must be 'propagate' or 'skip', "
                f"got {cfg.on_nonfinite!r}"
            )
        # A 2-D ("cells", "model") mesh with |model| > 1 switches the round
        # pipeline to the model-sharded hot path (core.pofl.ModelShard):
        # explicit shard_map blocks over the model axis, so — unlike the
        # cells axis, where input placement alone partitions the program —
        # the engine must know about it. |model| == 1 (incl. the 1-D mesh)
        # keeps model_shard None and the trace bit-identical to unsharded.
        self._model_shard = None
        if (
            mesh is not None
            and "model" in getattr(mesh, "axis_names", ())
            and int(mesh.shape["model"]) > 1
        ):
            self._model_shard = ModelShard(mesh=mesh)
        # static observability config: flipping `diagnostics` selects a
        # different traced program, so it keys the engine cache (a
        # diagnostics engine never shares jit traces with the plain one)
        self.obs = obs or DEFAULT_OBS
        self.n_traces = 0  # chunk-scan trace counter (see class docstring)
        self.n_lattice_traces = 0  # lattice-program trace counter
        self.n_compiles = 0  # AOT lattice compiles (one per arg signature)
        self.compile_seconds = 0.0  # trace+compile wall time of those
        # Donating the carry on CPU only triggers "donation not implemented"
        # warnings; donate on accelerators where it buys in-place reuse.
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._chunk_jit = jax.jit(
            self._chunk, static_argnames=("n_steps",), donate_argnums=donate
        )
        self._donating = bool(donate)
        # Under a model-sharded mesh the cell vmap must NAME its batch axis
        # (spmd_axis_name): the shard_map blocks inside the cell body are
        # manual over BOTH mesh axes, so the vmapped dimension has to map
        # onto the "cells" axis explicitly. Unsharded/|model|==1 engines
        # keep the anonymous vmap — the seed's exact trace.
        vmap_kw = {}
        if self._model_shard is not None:
            vmap_kw["spmd_axis_name"] = mesh.axis_names[0]
        self._lattice_jit = jax.jit(
            jax.vmap(
                self._lattice_cell, in_axes=(None, None, None, 0, 0, 0),
                **vmap_kw,
            )
        )
        self._fused_lattice_jit = jax.jit(
            jax.vmap(
                self._fused_lattice_cell,
                in_axes=(None, None, None, 0, 0, 0, 0),
                **vmap_kw,
            )
        )
        self._fused_alg_lattice_jit = jax.jit(
            jax.vmap(
                self._fused_alg_lattice_cell,
                in_axes=(None, None, None, 0, 0, 0, 0, 0),
                **vmap_kw,
            )
        )
        # the CHUNKED program family (sim.resilience): init and scan are
        # separate executables so a sweep can re-enter from a persisted
        # carry. Both are policy-fused; *_alg adds the traced algorithm axis.
        self._init_lattice_jit = jax.jit(
            jax.vmap(self._init_lattice_cell, in_axes=(None, 0), **vmap_kw)
        )
        self._init_alg_lattice_jit = jax.jit(
            jax.vmap(self._init_alg_lattice_cell, in_axes=(None, 0), **vmap_kw)
        )
        self._chunk_lattice_jit = jax.jit(
            jax.vmap(
                self._chunk_lattice_cell,
                in_axes=(0, None, None, None, 0, 0, 0, 0),
                **vmap_kw,
            )
        )
        self._chunk_alg_lattice_jit = jax.jit(
            jax.vmap(
                self._chunk_alg_lattice_cell,
                in_axes=(0, None, None, None, 0, 0, 0, 0, 0),
                **vmap_kw,
            )
        )
        # AOT ``lower().compile()`` executable cache: arg signature →
        # compiled lattice program (see :meth:`_aot_lattice_executable`).
        # Bounded LRU, same rationale as PR 4's gather-jit cache: each entry
        # pins a full XLA executable, so a long-lived process sweeping many
        # lattice shapes must evict, not accumulate.
        self._lattice_executables: OrderedDict[tuple, Any] = OrderedDict()

    # -- state construction -------------------------------------------------

    def init(self, params0, seed, fused_algorithms: bool = False) -> SimState:
        """Initial carry. ``seed`` may be traced (lattice vmaps over it).

        ``fused_algorithms=True`` (the traced-``algorithm_id`` lattice cell)
        builds the FULL :class:`~repro.core.local_update.AlgState` — every
        ``lax.switch`` branch is traced, so the carry must hold the union of
        all algorithms' state. Otherwise the state follows the static
        ``cfg.local_algorithm`` (``None`` — an empty subtree — for stateless
        algorithms, keeping the legacy carry structure bitwise)."""
        key = jax.random.PRNGKey(seed)
        k_chan_init, key = jax.random.split(key)
        chan = self.process.init(k_chan_init)
        return SimState(
            params=params0, key=key, chan=chan,
            alg=self._init_alg_state(params0, fused_algorithms),
        )

    def _init_alg_state(self, params0, fused_algorithms: bool):
        full = fused_algorithms or self.cfg.local_algorithm == FUSED_ALGORITHM
        if not full and self.cfg.local_algorithm in local_update.STATELESS:
            return None  # zero new leaves, zero new ops — the legacy carry
        # static size only (no ravel ops enter the trace for the zeros init)
        dim = sum(
            int(np.prod(np.shape(leaf))) for leaf in jax.tree.leaves(params0)
        )
        return local_update.init_state(
            self.cfg.local_algorithm, self.cfg.n_devices, dim, full=full
        )

    # -- the scanned program ------------------------------------------------

    def scan_rounds(
        self,
        state: SimState,
        t_ints: jnp.ndarray,       # (T,) int32 round indices
        do_eval: jnp.ndarray,      # (T,) bool — run eval_fn this round
        noise_power=None,          # traced scalar or None → cfg.noise_power
        alpha=None,                # traced scalar or None → cfg.alpha
        active: jnp.ndarray | None = None,  # (T,) bool — mask padded rounds
        policy_id=None,            # traced int32 or None → cfg.policy string
        algorithm_id=None,         # traced int32 or None → cfg.local_algorithm
        fault_round=None,          # traced int32 or None → no injection hook
    ) -> tuple[SimState, RoundRecord]:
        """Pure scan over rounds; vmap-safe (xs stay unbatched, so the eval
        ``lax.cond`` remains a genuine branch, not a select).

        ``active=None`` (the lattice path) scans every round unconditionally.
        With an ``active`` mask (the ``run_with_history`` static-length
        path), inactive rounds are genuine ``lax.cond`` no-ops: the carry —
        params, PRNG chain, channel state — passes through untouched, so a
        padded scan of the same active prefix is bit-identical to an unpadded
        one.

        ``fault_round`` (``sim.resilience``'s NaN-injection hook, a traced
        per-cell int32) rides into ``round_algorithm`` as a VALUE — ``-1``
        never fires — so faulted and unfaulted cells share one program;
        ``None`` (every pre-existing path) adds no ops at all.
        """

        def round_body(st: SimState, t_int, ev):
            t = t_int.astype(jnp.float32)
            key, k_round = jax.random.split(st.key)
            k_batch, k_chan, k_sched, k_noise = jax.random.split(k_round, 4)
            with jax.named_scope("channel"):
                chan, h, avail = self.process.step(st.chan, k_chan)
            params, alg, m = round_algorithm(
                self.loss_fn, self.data, self.cfg, st.params, h,
                k_batch, k_sched, k_noise, t,
                noise_power=noise_power, alpha=alpha,
                # processes that never drop skip the masking entirely →
                # bit-identical to the legacy static path
                avail=avail if self.process.can_drop else None,
                policy_id=policy_id,
                diagnostics=self.obs.diagnostics,
                model_shard=self._model_shard,
                alg_state=st.alg,
                algorithm_id=algorithm_id,
                fault_round=fault_round,
            )
            with jax.named_scope("eval"):
                ev_rec = None
                if self.eval_fn is None:
                    loss = acc = jnp.zeros(())
                elif self._task_eval is not None:
                    # model-task eval: one cond produces the full EvalRecord; its
                    # loss/acc also fill the legacy always-present record fields
                    ev_rec = jax.lax.cond(
                        ev,
                        self._task_eval.record,
                        lambda p: zero_eval_record(),
                        params,
                    )
                    loss, acc = ev_rec.loss, ev_rec.acc
                else:
                    loss, acc = jax.lax.cond(
                        ev,
                        lambda p: tuple(
                            jnp.asarray(v, jnp.float32) for v in self.eval_fn(p)
                        ),
                        lambda p: (jnp.zeros(()), jnp.zeros(())),
                        params,
                    )
            rec = RoundRecord(
                e_com=m.e_com, e_var=m.e_var, grad_norm=m.grad_norm,
                n_scheduled=m.n_scheduled, loss=loss, acc=acc, diag=m.diag,
                eval=ev_rec, health=m.health,
            )
            return SimState(params=params, key=key, chan=chan, alg=alg), rec

        if active is None:

            def body(st, x):
                t_int, ev = x
                return round_body(st, t_int, ev)

            xs: tuple = (t_ints, do_eval)
        else:

            def body(st, x):
                t_int, ev, act = x
                return jax.lax.cond(
                    act,
                    lambda s: round_body(s, t_int, ev),
                    lambda s: (
                        s,
                        _zero_record(
                            self.obs.diagnostics, self._task_eval is not None,
                            self.cfg.on_nonfinite == "skip",
                        ),
                    ),
                    st,
                )

            xs = (t_ints, do_eval, active)

        return jax.lax.scan(body, state, xs)

    # -- the vmapped lattice program ----------------------------------------

    def _lattice_cell(self, params0, t_ints, do_eval, noise_power, alpha, seed):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        state = self.init(params0, seed)
        _, recs = self.scan_rounds(
            state, t_ints, do_eval, noise_power=noise_power, alpha=alpha
        )
        return recs

    def _fused_lattice_cell(
        self, params0, t_ints, do_eval, noise_power, alpha, seed, policy_id
    ):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        state = self.init(params0, seed)
        _, recs = self.scan_rounds(
            state, t_ints, do_eval, noise_power=noise_power, alpha=alpha,
            policy_id=policy_id,
        )
        return recs

    def _fused_alg_lattice_cell(
        self, params0, t_ints, do_eval, noise_power, alpha, seed,
        policy_id, algorithm_id,
    ):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        state = self.init(params0, seed, fused_algorithms=True)
        _, recs = self.scan_rounds(
            state, t_ints, do_eval, noise_power=noise_power, alpha=alpha,
            policy_id=policy_id, algorithm_id=algorithm_id,
        )
        return recs

    # -- the chunked (checkpointable) lattice program family ---------------
    # sim.resilience splits init and scan into separate executables: the
    # init program builds the batched carry once, the chunk program advances
    # it `len(t_ints)` rounds and RETURNS it — so the full donated carry can
    # be persisted between chunks and re-entered bit-identically.

    def _init_lattice_cell(self, params0, seed):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        return self.init(params0, seed)

    def _init_alg_lattice_cell(self, params0, seed):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        return self.init(params0, seed, fused_algorithms=True)

    def _chunk_lattice_cell(
        self, state, t_ints, do_eval, active, noise_power, alpha,
        policy_id, fault_round,
    ):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        return self.scan_rounds(
            state, t_ints, do_eval, noise_power=noise_power, alpha=alpha,
            active=active, policy_id=policy_id, fault_round=fault_round,
        )

    def _chunk_alg_lattice_cell(
        self, state, t_ints, do_eval, active, noise_power, alpha,
        policy_id, algorithm_id, fault_round,
    ):
        self.n_lattice_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.lattice_traces")
        return self.scan_rounds(
            state, t_ints, do_eval, noise_power=noise_power, alpha=alpha,
            active=active, policy_id=policy_id, algorithm_id=algorithm_id,
            fault_round=fault_round,
        )

    def init_lattice_states(
        self, params0, seed_b, fused_algorithms: bool = False
    ) -> SimState:
        """The batched initial carry for a chunked lattice run: ONE compiled
        ``vmap(init)`` dispatch over the flattened (B,) seed axis. The
        returned :class:`SimState` has every leaf batched on axis 0 — exactly
        the carry :meth:`run_lattice_chunk` advances — and doubles as the
        structure/sharding TEMPLATE a persisted checkpoint is restored into
        (``repro.checkpoint.load_pytree`` re-places leaves onto it, keeping
        the chunk executable's argument signature stable across resume)."""
        args = (jax.tree.map(jnp.asarray, params0), jnp.asarray(seed_b))
        mode = "init_alg" if fused_algorithms else "init"
        compiled = self._aot_lattice_executable(mode, args)
        return compiled(*args)

    def run_lattice_chunk(
        self, state_b: SimState, t_ints, do_eval, active,
        noise_b, alpha_b, policy_b, algorithm_b=None, fault_b=None,
    ) -> tuple[SimState, RoundRecord]:
        """Advance the batched carry ``len(t_ints)`` rounds → (carry', records).

        The chunked counterpart of :meth:`run_lattice_cells`: same vmapped
        cell axes (always policy-fused — a constant ``policy_b`` is fine),
        but the carry comes IN as an argument and comes BACK OUT, so
        ``sim.resilience`` can persist it between chunks. ``active`` masks
        padded tail rounds as genuine carry-preserving no-ops, so every chunk
        of a sweep — including a short final one — dispatches the SAME
        executable (one compile per signature; AOT-cached like the other
        modes). ``fault_b`` is the per-cell NaN-injection round (int32, -1 =
        never; defaults to all -1 — same program either way, it is an input
        value). Chunking is re-entry, not re-tracing: the carry holds the
        whole PRNG chain, so chunked and resumed runs replay identical
        per-round keys.
        """
        if policy_b is None:
            raise ValueError(
                "run_lattice_chunk is always policy-fused: pass policy_b "
                "(a constant array selects one policy)"
            )
        n_cells = int(np.shape(policy_b)[0]) if np.ndim(policy_b) else 1
        # the launch, as in :meth:`run_lattice_cells`
        with span("lattice.dispatch", fused=True, cells=n_cells, chunked=True):
            if fault_b is None:
                fault_b = jnp.full(np.shape(policy_b), -1, jnp.int32)
            args = (
                state_b, jnp.asarray(t_ints), jnp.asarray(do_eval),
                jnp.asarray(active), noise_b, alpha_b, policy_b,
            )
            if algorithm_b is not None:
                mode = "chunk_alg"
                args = args + (algorithm_b, jnp.asarray(fault_b))
            else:
                mode = "chunk"
                args = args + (jnp.asarray(fault_b),)
            compiled = self._aot_lattice_executable(mode, args)
            return compiled(*args)

    @staticmethod
    def _arg_signature(leaf) -> tuple:
        """Hashable AOT-dispatch identity of one lattice argument: shape,
        dtype, weak-typedness, and placement (a committed ``NamedSharding``
        compiles a different — partitioned — program than the default
        single-device placement; jax shardings hash by device layout, so two
        equal meshes share a signature). Must never touch the leaf's VALUES:
        a process-spanning global array cannot be fetched."""
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:  # non-array leaf (never a global array)
            dtype = np.asarray(leaf).dtype
        return (
            tuple(np.shape(leaf)),
            str(dtype),
            bool(getattr(leaf, "weak_type", False)),
            getattr(leaf, "sharding", None),
        )

    def _aot_lattice_executable(self, mode, args: tuple):
        """The compiled lattice program for ``args`` — AOT, cached, counted.

        ``mode`` selects the jitted vmap program — ``False`` (plain cells),
        ``True`` (policy-fused), ``"fused_alg"`` (policy+algorithm-fused),
        ``"init"``/``"init_alg"`` (the chunked family's batched-carry init),
        ``"chunk"``/``"chunk_alg"`` (the carry-in/carry-out chunk scan) —
        and leads the executable key. The mode values are APPEND-ONLY (like
        the signature tuple itself): the historical ``False``/``True``
        entries keep their exact keys, new program families add new values.

        First call for an argument signature pays ``jit.lower(...).compile()``
        ONCE (wall time accumulated in ``compile_seconds``, count in
        ``n_compiles``) and keeps the resulting executable; repeats dispatch
        straight to it — no jit-cache lookup, no re-trace, and honest
        compile-vs-steady-state accounting for ``benchmarks/run.py``. The
        executable also exposes XLA's per-program ``cost_analysis`` /
        ``memory_analysis`` (see :meth:`lattice_cost_analysis`). Each compile
        adds 0 or 1 to ``lattice.cache_misses`` (1 when it missed JAX's
        persistent cache), sets the gauges ``lattice.lane_dense_leaves`` and
        ``lattice.lane_dense_elems`` (the gradient leaves and elements its
        rounds carry lane-dense, ``core.grad_layout``) and registers its
        modules with ``repro.obs.stages``, so a profiler trace of the
        program can be read stage by stage.
        """
        leaves, treedef = jax.tree.flatten(args)
        # mesh identity rides at the END of the key (append-only contract):
        # the engine cache already separates meshed engines, but the
        # executables of a shared-signature argset must still never alias
        # across mesh shapes if an engine is ever built bypassing the cache
        key = (
            mode, treedef, tuple(self._arg_signature(l) for l in leaves),
            _mesh_key(self.mesh),
        )
        compiled = self._lattice_executables.get(key)
        if compiled is None:
            fn = {
                False: self._lattice_jit,
                True: self._fused_lattice_jit,
                "fused_alg": self._fused_alg_lattice_jit,
                "init": self._init_lattice_jit,
                "init_alg": self._init_alg_lattice_jit,
                "chunk": self._chunk_lattice_jit,
                "chunk_alg": self._chunk_alg_lattice_jit,
            }[mode]
            install_listener()
            misses0 = metric_value("compile_cache.misses")
            t0 = time.perf_counter()
            with span("lattice.compile", fused=bool(mode)):
                compiled = fn.lower(*args).compile()
            dt = time.perf_counter() - t0
            self.compile_seconds += dt
            self.n_compiles += 1
            counter_add("lattice.n_compiles")
            counter_add("lattice.compile_seconds", dt, emit_event=False)
            # 0 or 1: did this compile miss the persistent cache?
            counter_add(
                "lattice.cache_misses",
                int(metric_value("compile_cache.misses") > misses0),
            )
            if mode not in ("init", "init_alg"):
                # how much of the gradient block this program carries
                # lane-dense (core.grad_layout); 0 for the flat block
                params = args[0]
                if mode in ("chunk", "chunk_alg"):  # the carry, batched over cells
                    params = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                        params.params,
                    )
                layout = lane_dense_layout(
                    self.cfg, params, self._model_shard,
                    traced_algorithm=mode in ("fused_alg", "chunk_alg"),
                )
                gauge_set("lattice.lane_dense_leaves", layout.n_dense if layout else 0)
                gauge_set("lattice.lane_dense_elems", layout.dense_elems if layout else 0)
            # the op -> round-stage map of this program, for trace readers
            # (``repro.obs.stages``; rendered only when first read)
            for module in compiled.runtime_executable().hlo_modules():
                register_module(module.name, module.to_string)
            self._lattice_executables[key] = compiled
            while len(self._lattice_executables) > _LATTICE_EXECUTABLES_MAX:
                self._lattice_executables.popitem(last=False)
        else:
            self._lattice_executables.move_to_end(key)
        return compiled

    def run_lattice_cells(
        self, params0, t_ints, do_eval, noise_b, alpha_b, seed_b,
        policy_b=None, algorithm_b=None,
    ) -> RoundRecord:
        """One compiled (vmap-over-cells ∘ scan-over-rounds) dispatch.

        ``noise_b``/``alpha_b``/``seed_b`` are the flattened (B,) cell axes;
        when they carry a ``NamedSharding`` over a cell mesh (see
        ``sim.lattice``) the whole program partitions along that axis —
        computation follows the committed input placement, so the engine
        needs no sharded/unsharded code split. ``policy_b`` (flattened (B,)
        int32 ``scheduling.POLICY_IDS``) switches to the POLICY-FUSED
        program: the policy becomes one more vmapped cell axis, so a whole
        multi-policy lattice is ONE compile. ``algorithm_b`` (flattened (B,)
        int32 ``local_update.ALGORITHM_IDS``, requires ``policy_b``) switches
        further to the policy+ALGORITHM-fused program — the local-update
        algorithm joins the vmapped cell axes, so a whole (algorithm × policy
        × noise × α × seed) lattice is still ONE compile. Dispatch is AOT
        (``lower().compile()`` on first signature, cached executable after),
        so repeat calls through :func:`cached_engine` re-trace zero times
        (``n_lattice_traces`` stays flat) and recompile zero times
        (``n_compiles`` stays flat).
        """
        if algorithm_b is not None and policy_b is None:
            raise ValueError(
                "algorithm_b requires policy_b: the algorithm-fused "
                "program fuses the policy axis too (constant policy_b "
                "is fine)"
            )
        n_cells = int(np.shape(seed_b)[0]) if np.ndim(seed_b) else 1
        # the dispatch span covers the host's whole launch: argument
        # conversion, the executable lookup (a first call compiles there,
        # inside its own ``lattice.compile`` span) and the asynchronous call;
        # the device's work completes under the caller's ``lattice.wait``
        with span("lattice.dispatch", fused=policy_b is not None, cells=n_cells):
            args = (
                jax.tree.map(jnp.asarray, params0),
                jnp.asarray(t_ints), jnp.asarray(do_eval),
                noise_b, alpha_b, seed_b,
            )
            if algorithm_b is not None:
                mode = "fused_alg"
                args = args + (policy_b, algorithm_b)
            elif policy_b is not None:
                mode = True
                args = args + (policy_b,)
            else:
                mode = False
            compiled = self._aot_lattice_executable(mode, args)
            return compiled(*args)

    def lattice_cost_analysis(self) -> dict:
        """XLA ``cost_analysis`` (flops/bytes) of the most recent lattice
        executable, as a flat dict ({} before the first compile)."""
        if not self._lattice_executables:
            return {}
        return dict(next(reversed(self._lattice_executables.values())).cost_analysis())

    def lattice_memory_analysis(self):
        """XLA ``memory_analysis`` (argument/output/temp bytes) of the most
        recent lattice executable, or None before the first compile."""
        if not self._lattice_executables:
            return None
        return next(reversed(self._lattice_executables.values())).memory_analysis()

    def _chunk(self, state: SimState, t0, n_active, n_steps: int):
        self.n_traces += 1  # Python body runs only when (re)tracing
        counter_add("engine.traces")
        steps = jnp.arange(n_steps, dtype=jnp.int32)
        t_ints = t0 + steps
        do_eval = jnp.zeros((n_steps,), bool)
        return self.scan_rounds(
            state, t_ints, do_eval, active=steps < n_active
        )

    # -- run_pofl-compatible driver -----------------------------------------

    def run_with_history(
        self,
        params0,
        n_rounds: int,
        eval_fn: Callable | None = None,
        eval_every: int = 5,
        seed: int | None = None,
    ) -> tuple[Any, History]:
        """Chunked scan with host-side eval between chunks → (params, History).

        ``eval_fn`` may be any Python callable (it never enters the trace);
        metrics sync to host once per chunk instead of once per round.
        ``seed`` defaults to ``cfg.seed`` (cached engines are shared across
        seeds, so ``run_pofl`` passes the current call's seed explicitly).

        Compile-cost note: every segment between eval boundaries runs as ONE
        static-length scan (length = the longest segment) with an active-mask
        prefix, so a cold call traces the scan exactly once — and repeat
        calls through :func:`cached_engine` trace zero times. Sweeps should
        still use ``sim.lattice`` (one compile per policy for ALL cells).
        """
        params0 = jax.tree.map(jnp.asarray, params0)
        if self._donating:
            params0 = jax.tree.map(lambda x: jnp.array(x, copy=True), params0)
        seed = self.cfg.seed if seed is None else seed
        state = self.init(params0, seed)

        hist = History(loss=[], e_com=[], e_var=[], test_acc=[], test_round=[])
        if eval_fn is None:
            eval_ts: list[int] = []
        else:
            eval_ts = sorted(
                {t for t in range(n_rounds) if t % eval_every == 0}
                | ({n_rounds - 1} if n_rounds else set())
            )

        # segment boundaries: one host sync after each eval round + the tail
        segments: list[tuple[int, int]] = []  # (t0, n_active)
        t = 0
        for stop in [et + 1 for et in eval_ts] + [n_rounds]:
            if stop > t:
                segments.append((t, stop - t))
                t = stop
        n_steps = max((n for _, n in segments), default=0)

        t = 0
        for t0, n_active in segments:
            state, recs = self._chunk_jit(
                state,
                jnp.asarray(t0, jnp.int32),
                jnp.asarray(n_active, jnp.int32),
                n_steps=n_steps,
            )
            hist.e_com.extend(np.asarray(recs.e_com)[:n_active].tolist())
            hist.e_var.extend(np.asarray(recs.e_var)[:n_active].tolist())
            t = t0 + n_active
            if eval_fn is not None and t - 1 in eval_ts and t - 1 not in hist.test_round:
                loss, acc = eval_fn(state.params)
                hist.loss.append(float(loss))
                hist.test_acc.append(float(acc))
                hist.test_round.append(t - 1)
        return state.params, hist


# --------------------------------------------------------------------------
# cross-call engine cache
# --------------------------------------------------------------------------

_ENGINE_CACHE: OrderedDict[tuple, SimEngine] = OrderedDict()
_ENGINE_CACHE_MAX = 64
# hit/miss counters live in the obs registry under ``engine_cache.`` —
# :func:`engine_cache_stats` stays as the thin shim the tests/benchmarks use


def _data_key(data: DeviceData) -> tuple:
    """Identity key for a stacked dataset (object identity + shape guard)."""
    ns = data.n_samples
    return (
        id(data.features),
        id(data.labels),
        None if ns is None else id(ns),
        tuple(np.shape(data.features)),
        tuple(np.shape(data.labels)),
    )


def _freeze(obj):
    """Recursively hashable view of a scenario-params value: dicts become
    sorted item tuples, lists/tuples become tuples, arrays (numpy or jax)
    become (tag, dtype, shape, values) tuples — so any params SimEngine
    accepts also key the cache instead of raising TypeError."""
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    if isinstance(obj, (np.ndarray, np.generic, jax.Array)):
        arr = np.asarray(obj)
        return ("arr", str(arr.dtype), arr.shape, tuple(arr.ravel().tolist()))
    return obj


def _mesh_key(mesh) -> tuple | None:
    """Hashable identity of a ``jax.sharding.Mesh`` (None stays None).

    Axis names, logical shape, and the flat (device id, owning process)
    pairs — two meshes over the same devices in the same layout are the same
    engine, anything else (different device set, different order, devices
    from a different process span) is not.
    """
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(np.shape(mesh.devices)),
        tuple((d.id, d.process_index) for d in np.ravel(mesh.devices)),
    )


def _process_topology_key() -> tuple:
    """The process topology this engine's traces were built under.

    A ``jax.distributed`` run compiles SPMD programs against the global
    device count and this process's rank, so traces from one topology must
    never be replayed under another — within one process lifetime the
    topology cannot change, but the key keeps the cache honest (and its
    entries debuggable) all the same.
    """
    return (jax.process_count(), jax.process_index())


def cached_engine(
    loss_fn: Callable,
    data: DeviceData,
    cfg: POFLConfig,
    channel_cfg: ChannelConfig | None = None,
    scenario: str = "static_rayleigh",
    scenario_params: dict | None = None,
    eval_fn: Callable | None = None,
    mesh: Any | None = None,
    obs: ObsConfig | None = None,
) -> SimEngine:
    """Return a (possibly shared) :class:`SimEngine` for this task + config.

    The key is ``(loss_fn, data identity, cfg with seed zeroed — including
    the aggregation backend — channel_cfg, scenario, eval_fn identity, mesh
    identity, process topology, obs config)``: calls that differ only by seed
    share one engine and therefore every jit trace it has already paid for.
    Model tasks (``repro.sim.tasks``) key by the same identities — a
    :func:`~repro.sim.tasks.make_model_task` task is memoized, so its
    ``loss_fn``/``data``/``TaskEval`` objects (and hence this cache entry)
    are stable across rebuilds of the same task arguments. A
    mesh-keyed engine never collides with the unsharded one (or with a
    differently-shaped mesh, or one spanning a different ``jax.distributed``
    process set), so per-engine trace counters stay meaningful under
    sharding. An ``obs`` with diagnostics on is a SECOND cache key for the
    same task — the taps change the traced program, so the diagnostics
    engine accumulates its own traces/executables; repeat diagnostics calls
    still re-trace zero times. The
    cache is a bounded LRU (evicts least recently used); entries pin their
    ``data`` arrays alive, which is the point — eviction releases them.
    """
    obs = obs or DEFAULT_OBS
    key = (
        loss_fn,
        _data_key(data),
        dataclasses.replace(cfg, seed=0),
        channel_cfg,
        scenario,
        _freeze(scenario_params),
        eval_fn,
        _mesh_key(mesh),
        _process_topology_key(),
        obs,
        # the fused backend's dispatch reads this env var at trace time, so
        # toggling it must not replay a stale trace (parity tests flip it)
        os.environ.get("REPRO_PALLAS_INTERPRET", ""),
    )
    engine = _ENGINE_CACHE.get(key)
    if engine is not None:
        counter_add("engine_cache.hits")
        _ENGINE_CACHE.move_to_end(key)
        return engine
    counter_add("engine_cache.misses")
    engine = SimEngine(
        loss_fn, data, cfg,
        channel_cfg=channel_cfg,
        scenario=scenario,
        scenario_params=scenario_params,
        eval_fn=eval_fn,
        mesh=mesh,
        obs=obs,
    )
    _ENGINE_CACHE[key] = engine
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_MAX:
        _ENGINE_CACHE.popitem(last=False)
    return engine


def engine_cache_stats() -> dict:
    """Snapshot of the cross-call engine cache: hits/misses/size.

    Thin shim over the obs registry (``engine_cache.hits`` / ``.misses``) —
    kept so every historical caller and test keeps working unchanged.
    """
    return {
        "hits": int(metric_value("engine_cache.hits")),
        "misses": int(metric_value("engine_cache.misses")),
        "size": len(_ENGINE_CACHE),
    }


def lattice_memory_stats() -> dict:
    """Per-device HBM footprint of the most recent AOT lattice executable
    across the cached engines: ``{"per_device_hbm_bytes", "argument_bytes",
    "output_bytes", "temp_bytes", "mesh_shape"}`` (zeros / None before any
    compile). XLA's ``memory_analysis`` is already PER-DEVICE under SPMD
    partitioning, so ``per_device_hbm_bytes = argument + output + temp`` is
    the number ``BENCH_sim.json`` reports — it shrinks as the model axis
    grows at fixed D.
    """
    stats = {
        "per_device_hbm_bytes": 0,
        "argument_bytes": 0,
        "output_bytes": 0,
        "temp_bytes": 0,
        "mesh_shape": None,
    }
    # most recently *used* executable across engines: walk engines in cache
    # (LRU) order, newest last, and take the last one holding an executable
    for engine in _ENGINE_CACHE.values():
        mem = engine.lattice_memory_analysis()
        if mem is None:
            continue
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
        out_b = int(getattr(mem, "output_size_in_bytes", 0))
        tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
        stats = {
            "per_device_hbm_bytes": arg_b + out_b + tmp_b,
            "argument_bytes": arg_b,
            "output_bytes": out_b,
            "temp_bytes": tmp_b,
            "mesh_shape": (
                None if engine.mesh is None
                else tuple(int(engine.mesh.shape[a]) for a in engine.mesh.axis_names)
            ),
        }
    return stats


def latest_lattice_executable():
    """The most recently used AOT lattice executable across the cached
    engines (None before any compile). Its ``as_text()`` is the optimized
    HLO — a compiled Pallas kernel shows there as a ``tpu_custom_call``,
    the jnp reference and interpret mode leave none — and its
    ``output_shardings`` say which devices hold the records."""
    latest = None
    for engine in _ENGINE_CACHE.values():
        if engine._lattice_executables:
            latest = next(reversed(engine._lattice_executables.values()))
    return latest


def lattice_compile_stats() -> dict:
    """Aggregate AOT lattice-compile counters over every cached engine:
    ``{"n_compiles", "compile_seconds"}`` — the compile-vs-steady-state split
    ``benchmarks/run.py`` reports (engines dropped by ``reset_engine_cache``
    leave the aggregate, so scope a measurement with a reset first)."""
    engines = list(_ENGINE_CACHE.values())
    return {
        "n_compiles": sum(e.n_compiles for e in engines),
        "compile_seconds": sum(e.compile_seconds for e in engines),
    }


def reset_engine_cache() -> None:
    """Drop every cached engine and zero the hit/miss counters.

    Scoped: resets exactly the ``engine_cache.`` registry namespace —
    never the persistent-compile-cache counters (a CI warm-run guard reads
    those across the whole process lifetime) or span totals.
    """
    _ENGINE_CACHE.clear()
    reset_metrics("engine_cache.")
