"""Experiment lattices: whole paper sweeps as ONE vmapped+scanned program.

A :class:`LatticeSpec` names the sweep axes

    algorithms × policies × noise_powers × alphas × seeds   (× n_rounds scanned)

and :func:`run_lattice` compiles the ENTIRE lattice into a single program:
``vmap`` over the flattened (algorithm, policy, noise, alpha, seed) grid of
the engine's ``lax.scan`` over rounds. The policy axis is *traced* — each
cell carries an int32 ``policy_id`` dispatched by ``lax.switch``
(``core.scheduling.scheduling_probs_by_id``), so a 5-policy sweep pays ONE
trace and ONE XLA compile instead of five (the engine cache likewise holds
one entry per lattice, keyed by the ``FUSED_POLICY`` sentinel). The
local-update algorithm axis is traced the same way — a multi-algorithm
``spec.algorithms`` gives every cell an int32 ``algorithm_id`` dispatched
through ``core.local_update``'s append-only branch table (engine cache
keyed by the ``FUSED_ALGORITHM`` sentinel), so (algorithm × policy × noise
× α × seed) is STILL one trace and one compile; a single-algorithm spec
(the default ``("fedavg",)``) keeps the historical static dispatch and
traces today's exact program.
``fuse_policies=False`` keeps the per-policy Python loop (one compile per
policy, each over the same traced-dispatch cell program with a constant
``policy_id``) — pinned bit-identical to the fused path by
tests/test_fused_lattice.py. The historical ``cfg.policy`` STRING dispatch
remains the round engine's default (``run_pofl`` trajectories are pinned on
it) and is pinned against the traced dispatch bitwise at the
``scheduling_probs`` level; whole-lattice string-vs-switch comparisons are
dtype-exact up to the documented ≤1-ULP cross-program reduction wobble
(same phenomenon as the PR-4 multi-host ``e_var`` carve-out). Anything
shape-changing (n_devices, |S|, samplers) remains structural either way. Per-cell metrics
stay on device for the whole run and stream out exactly once at the end as
structured numpy records.

Compared to looping ``run_pofl`` over (policy × trial × sweep-point) — the
seed repo's benchmark harness — this removes the per-round host sync and the
per-(trial, sweep-point) recompiles; see benchmarks/run.py's ``BENCH_sim``
entry for the measured cells/sec (``compile_seconds`` vs
``steady_cells_per_sec`` — dispatch is AOT ``lower().compile()`` on the
engine, and ``repro.sim.compile_cache`` can persist the compiles across
processes).

Sharding: ``run_lattice(..., mesh=...)`` places the flattened cell axis —
which now spans policies too — on a ``jax.sharding.Mesh`` with
``NamedSharding(P("cells"))``: the grid is padded to a multiple of the mesh
size with dead cells (repeats of the last real cell) whose outputs are
masked off at unpadding, and the same vmapped+scanned program is reused
unchanged, so a 1-device mesh is bit-identical to the unsharded path (pinned
by tests/test_lattice_sharded.py). ``mesh`` may be a Mesh, a device count
(→ :func:`make_cell_mesh`), or None. Engines are cached across calls by
``sim.engine.cached_engine`` keyed on the mesh identity, so repeat sharded
calls re-trace zero times.

Multi-host: when the mesh spans processes (``jax.distributed`` initialized —
see ``repro.sim.multihost`` — and a global-device mesh from
:func:`~repro.sim.multihost.make_global_cell_mesh`), every process makes the
SAME ``run_lattice`` call but feeds only its addressable shard of the padded
cell grid (``shard_to_global`` assembly) and receives the full records back
via a tiled allgather (``gather_records``), so the returned
:class:`LatticeRecords` is identical on every host — dtype-exact against the
single-host run of the same spec, pinned by tests/test_multihost_lattice.py
through the ``repro.launch.distributed`` subprocess launcher.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import local_update, scheduling
from repro.core.channel import ChannelConfig
from repro.core.pofl import DeviceData, POFLConfig
from repro.obs.config import ObsConfig
from repro.obs.sink import emit
from repro.obs.spans import span
from repro.sim.engine import FUSED_ALGORITHM, FUSED_POLICY, cached_engine
from repro.sim.multihost import (
    cell_model_mesh_over,
    cells_mesh_over,
    gather_records,
    mesh_spans_processes,
    shard_to_global,
)

_LOCAL_MESH_HINT = "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count)"


def make_cell_model_mesh(
    cells: int | None = None, model: int = 1
) -> jax.sharding.Mesh:
    """A 2-D ``("cells", "model")`` mesh over the first ``cells × model``
    LOCAL devices.

    The cells axis shards the flattened lattice grid exactly like the 1-D
    mesh; a ``model`` axis > 1 additionally shards the flat model dimension
    D of every cell — gradients, noise draws, params carry and ŷ are placed
    ``P(None, "model")`` so each device holds only ``D/model`` of every
    large tensor (see ``core.pofl.ModelShard``). ``cells=None`` takes every
    full group of ``model`` local devices. Process-spanning meshes come from
    ``repro.sim.multihost.make_global_cell_model_mesh``; on CPU CI, fake
    multi-device semantics come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    return cell_model_mesh_over(
        jax.local_devices(), cells, model, hint=_LOCAL_MESH_HINT
    )


def make_cell_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """A 1-D ``("cells",)`` mesh over the first ``n_devices`` LOCAL devices.

    ``None`` takes every local device. Genuinely local: under
    ``jax.distributed`` this builds from ``jax.local_devices()`` (this
    process's own devices — ``jax.devices()`` would return rank 0's devices
    on every rank); process-spanning meshes come from
    ``repro.sim.multihost.make_global_cell_mesh`` instead. On CPU CI, fake
    multi-device semantics come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes).
    """
    return cells_mesh_over(
        jax.local_devices(), n_devices, hint=_LOCAL_MESH_HINT,
    )


@dataclasses.dataclass(frozen=True)
class LatticeSpec:
    """Sweep axes + schedule for one experiment lattice.

    ``noise_powers``, ``alphas`` and ``seeds`` are *vmapped* (batched into
    one program); ``policies`` is a Python loop (structural). Everything not
    named here comes from ``run_lattice``'s ``base_cfg``.
    """

    policies: tuple[str, ...] = ("pofl",)
    noise_powers: tuple[float, ...] = (1e-11,)
    alphas: tuple[float, ...] = (0.1,)
    seeds: tuple[int, ...] = (0,)
    n_rounds: int = 100
    eval_every: int = 5
    # local-update algorithms (core.local_update.ALGORITHMS names); the
    # default single-algorithm tuple keeps the historical static dispatch —
    # ≥2 names trace an int32 algorithm_id axis into the same fused program
    algorithms: tuple[str, ...] = ("fedavg",)

    @property
    def n_cells(self) -> int:
        return (
            len(self.algorithms)
            * len(self.policies)
            * len(self.noise_powers)
            * len(self.alphas)
            * len(self.seeds)
        )


class LatticeRecords(NamedTuple):
    """Structured per-cell records, axes (algorithm, policy, noise, alpha,
    seed, ...).

    The algorithm axis LEADS and is always present (size 1 for the default
    single-algorithm spec — legacy ``[p, n, a, s]`` indexing broadcasts
    unchanged). ``loss``/``acc`` are sub-sampled at ``eval_rounds`` (empty E
    axis when the lattice ran without an eval_fn).

    ``eval`` is the model-task eval subtree: a
    :class:`~repro.sim.tasks.EvalRecord` of ``(A, P, Nn, Na, Ns, E)`` curves
    when the lattice ran with a :class:`~repro.sim.tasks.TaskEval` eval_fn,
    else ``None`` — which flattens to an EMPTY pytree, so eval-off (and
    legacy-eval) records keep exactly the historical leaves (the ``diag``
    contract, applied to accuracy/loss curves).
    """

    axes: dict            # axis name -> coordinate list
    e_com: np.ndarray     # (A, P, Nn, Na, Ns, T)
    e_var: np.ndarray     # (A, P, Nn, Na, Ns, T)
    grad_norm: np.ndarray # (A, P, Nn, Na, Ns, T)
    n_scheduled: np.ndarray  # (A, P, Nn, Na, Ns, T)
    loss: np.ndarray      # (A, P, Nn, Na, Ns, E)
    acc: np.ndarray       # (A, P, Nn, Na, Ns, E)
    eval_rounds: np.ndarray  # (E,)
    diag: Any = None      # RoundDiagnostics of (A, P, Nn, Na, Ns, T) taps when
    #                       the lattice ran with ObsConfig(diagnostics=True)
    eval: Any = None      # tasks.EvalRecord of (A, P, Nn, Na, Ns, E) curves
    #                       when eval_fn was a tasks.TaskEval, else None
    health: Any = None    # core.metrics.RoundHealth of (A, P, Nn, Na, Ns, T)
    #                       quarantine counters when base_cfg.on_nonfinite=
    #                       "skip", else None (the diag empty-subtree contract)

    def cell(self, **coords) -> dict:
        """Select one sub-array per field by axis coordinates, e.g.
        ``records.cell(policy="pofl", seed=0)``."""
        idx: list[Any] = []
        for name in ("algorithm", "policy", "noise_power", "alpha", "seed"):
            if name in coords:
                idx.append(self.axes[name].index(coords.pop(name)))
            else:
                idx.append(slice(None))
        if coords:
            raise ValueError(f"unknown axes {sorted(coords)}")
        sel = tuple(idx)
        return {
            f: getattr(self, f)[sel]
            for f in ("e_com", "e_var", "grad_norm", "n_scheduled", "loss", "acc")
        }


def run_lattice(
    loss_fn: Callable,
    data: DeviceData,
    params0,
    spec: LatticeSpec,
    base_cfg: POFLConfig | None = None,
    eval_fn: Callable | None = None,
    channel_cfg: ChannelConfig | None = None,
    scenario: str = "static_rayleigh",
    scenario_params: dict | None = None,
    mesh: jax.sharding.Mesh | int | tuple | None = None,
    fuse_policies: bool = True,
    fuse_algorithms: bool = True,
    obs: ObsConfig | None = None,
    _forced_algorithm_axis: bool = False,
) -> LatticeRecords:
    """Run the full lattice; ONE compiled (vmap ∘ scan) program for the spec.

    Args:
      eval_fn: traceable ``params -> (loss, acc)`` — evaluated inside the
        scan every ``spec.eval_every`` rounds (and on the last round).
      base_cfg: defaults for everything the spec doesn't sweep; its
        ``policy``/``noise_power``/``alpha``/``seed``/``local_algorithm``
        fields are overridden per cell (``spec.algorithms`` names the
        algorithm axis, like ``spec.policies`` names the policy axis). ``base_cfg.backend`` selects the aggregation backend for
        every cell (under the cell vmap the ``pallas_fused`` kernel batches
        into the trial-batched grid), and ``data`` may carry heterogeneous
        shards (``DeviceData.n_samples``) — the Eq. 34/35/37 weights follow
        the true m_i/M in every cell.
      mesh: shard the flattened cell axis over this ``jax.sharding.Mesh``
        (inputs are placed with ``NamedSharding(P(<first axis>))``). An int
        builds ``make_cell_mesh(mesh)``; a ``(cells, model)`` tuple builds
        ``make_cell_model_mesh(cells, model)``. The grid is padded to a
        multiple of the CELLS axis size (the full device count on a 1-D
        mesh) with dead cells that are dropped on unpadding; records,
        order, and values are unchanged (a 1-device mesh is bit-identical
        to ``mesh=None``). A 2-D ``("cells", "model")`` mesh with
        ``|model| > 1`` additionally shards the flat model dimension: the
        engine pads D to a multiple of ``|model| · tile_d``, places every
        flat-D leaf ``P(None, "model")``, and routes stats/aggregation
        through model-axis ``shard_map`` (``core.pofl.ModelShard``); the
        initial params are placed by ``launch.sharding.param_spec``.
        A process-spanning mesh (``sim.multihost.make_global_cell_mesh`` /
        ``make_global_cell_model_mesh`` under ``jax.distributed``)
        switches input feeding to per-process shard assembly and records to
        an allgather — every host returns the same full records.
      fuse_policies: True (default) folds the policy axis into the traced
        program — every cell carries an int32 ``policy_id``, the whole
        lattice is one engine-cache entry / one trace / one compile. False
        restores the per-policy Python loop — each policy compiles its own
        (smaller) program over the same traced-dispatch cell body with a
        constant ``policy_id`` axis, so records are bit-identical to the
        fused path; kept as the debugging/fallback route.
      fuse_algorithms: True (default) folds a multi-algorithm
        ``spec.algorithms`` axis into the traced program the same way —
        every cell carries an int32 ``algorithm_id`` through
        ``core.local_update``'s append-only ``lax.switch`` table, so
        (algorithm × policy × noise × α × seed) is still ONE compile. False
        loops per algorithm — each algorithm runs its own lattice over the
        same traced-dispatch cell program with a constant ``algorithm_id``
        axis (one compile per algorithm), bit-identical to the fused lanes;
        the debugging/fallback route, mirroring ``fuse_policies=False``.
        Single-algorithm specs (the default) never trace the algorithm axis:
        the engine dispatches statically on ``cfg.local_algorithm`` and the
        default ``("fedavg",)`` spec traces today's exact program.
      obs: observability config. ``ObsConfig(diagnostics=True)`` compiles
        the cheap per-round taps (:class:`repro.core.metrics.RoundDiagnostics`)
        into every cell and returns them as ``LatticeRecords.diag``; it keys
        a SECOND engine-cache entry, so repeat diagnostics sweeps still
        re-trace zero times. ``None``/default: program and records identical
        to before obs existed. Every sweep also times itself
        (``span("lattice.sweep")``) and emits one ``lattice`` JSONL event per
        engine dispatch when ``REPRO_OBS_DIR`` is set.
    """
    base_cfg = base_cfg or POFLConfig(n_devices=data.n_devices)
    algs = tuple(spec.algorithms)
    if not algs:
        raise ValueError("spec.algorithms must name at least one algorithm")
    for a in algs:
        local_update.algorithm_id(a)  # fail fast on unknown names

    if len(algs) > 1 and not fuse_algorithms:
        # per-algorithm Python loop: each algorithm re-enters run_lattice as
        # a single-algorithm spec FORCED onto the traced-dispatch cell
        # program (constant algorithm_id axis) — same cell program as the
        # fused lanes, so records are bit-identical; one compile per
        # algorithm (mirrors the fuse_policies=False cost model)
        per_alg = [
            run_lattice(
                loss_fn, data, params0,
                dataclasses.replace(spec, algorithms=(a,)),
                base_cfg=base_cfg, eval_fn=eval_fn, channel_cfg=channel_cfg,
                scenario=scenario, scenario_params=scenario_params,
                mesh=mesh, fuse_policies=fuse_policies, obs=obs,
                _forced_algorithm_axis=True,
            )
            for a in algs
        ]
        return _concat_algorithms(algs, per_alg)

    with span(
        "lattice.sweep", fused=fuse_policies, policies=len(spec.policies),
        algorithms=len(algs),
    ) as sweep:
        return _run_sweep(
            loss_fn, data, params0, spec, base_cfg, eval_fn, channel_cfg,
            scenario, scenario_params, mesh, fuse_policies, obs, algs,
            _forced_algorithm_axis, sweep,
        )


def _run_sweep(
    loss_fn, data, params0, spec, base_cfg, eval_fn, channel_cfg, scenario,
    scenario_params, mesh, fuse_policies, obs, algs, forced_algorithm_axis,
    sweep,
) -> LatticeRecords:
    """The body of one :func:`run_lattice` sweep, inside its
    ``lattice.sweep`` span. The host work splits into child spans:
    ``lattice.place`` (the cell axes onto the devices), ``lattice.dispatch``
    (the engine), ``lattice.wait`` (until the device is done),
    ``lattice.stream_out`` (records to the host) and ``lattice.assemble``
    (the grid reshapes)."""
    # the algorithm axis is traced iff >1 algorithm (fused) or forced by the
    # per-algorithm fallback loop; single-algorithm user specs keep the
    # historical static dispatch (default ("fedavg",) → today's exact program)
    traced_algs = len(algs) > 1 or forced_algorithm_axis
    base_alg = FUSED_ALGORITHM if len(algs) > 1 else algs[0]

    if isinstance(mesh, int):
        mesh = make_cell_mesh(mesh)
    elif isinstance(mesh, tuple):
        mesh = make_cell_model_mesh(*mesh)

    t_ints = np.arange(spec.n_rounds, dtype=np.int32)
    if eval_fn is not None and spec.n_rounds:
        do_eval = (t_ints % spec.eval_every == 0) | (t_ints == spec.n_rounds - 1)
    else:
        do_eval = np.zeros(spec.n_rounds, bool)
    eval_rounds = t_ints[do_eval]

    # flattened vmap grid: (algorithm,) × (policy,) × noise × alpha × seed
    # when fused — algorithm-major then policy-major, so the fused flat order
    # equals the per-algorithm/per-policy stack orders
    grid_axes = [
        np.asarray(spec.noise_powers, np.float32),
        np.asarray(spec.alphas, np.float32),
        np.asarray(spec.seeds, np.int32),
    ]
    alg_ids = np.asarray(
        [local_update.algorithm_id(a) for a in algs], np.int32
    )
    if fuse_policies:
        pol_ids = np.asarray(
            [scheduling.policy_id(p) for p in spec.policies], np.int32
        )
        if traced_algs:
            grid_al, grid_p, grid_n, grid_a, grid_s = np.meshgrid(
                alg_ids, pol_ids, *grid_axes, indexing="ij"
            )
            cells = [
                grid_n.ravel(), grid_a.ravel(), grid_s.ravel(),
                grid_p.ravel(), grid_al.ravel(),
            ]
        else:
            grid_p, grid_n, grid_a, grid_s = np.meshgrid(
                pol_ids, *grid_axes, indexing="ij"
            )
            cells = [
                grid_n.ravel(), grid_a.ravel(), grid_s.ravel(), grid_p.ravel()
            ]
    elif traced_algs:
        grid_al, grid_n, grid_a, grid_s = np.meshgrid(
            alg_ids, *grid_axes, indexing="ij"
        )
        cells = [
            grid_n.ravel(), grid_a.ravel(), grid_s.ravel(), grid_al.ravel()
        ]
    else:
        grid_n, grid_a, grid_s = np.meshgrid(*grid_axes, indexing="ij")
        cells = [grid_n.ravel(), grid_a.ravel(), grid_s.ravel()]
    n_real = cells[0].size

    multihost = mesh_spans_processes(mesh)
    sweep.annotate(cells=n_real, multihost=multihost)
    with span("lattice.place"):
        if mesh is not None:
            # pad the cell axis to a multiple of the CELLS-axis size with dead
            # cells (repeats of the last real cell — same shapes, outputs
            # discarded). On a 1-D mesh that is the device count; on a 2-D
            # (cells, model) mesh only the first axis shards cells.
            n_shards = int(mesh.shape[mesh.axis_names[0]])
            pad = (-n_real) % n_shards
            if pad:
                cells = [np.concatenate([c, np.repeat(c[-1:], pad)]) for c in cells]
            cell_sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))

            if multihost:
                # every process holds the same deterministic grid; each commits
                # only the shards its own devices own
                def place(c):
                    return shard_to_global(c, cell_sharding)
            else:
                def place(c):
                    return jax.device_put(jnp.asarray(c), cell_sharding)

            if "model" in mesh.axis_names and int(mesh.shape["model"]) > 1:
                # model-sharded lattice: commit the initial params to their
                # param_spec placement so the very first dispatch — not just the
                # constrained carry — holds only D/|model| columns per device
                from repro.launch.sharding import param_spec  # late: launch↔sim

                def place_leaf(leaf):
                    sh = NamedSharding(mesh, param_spec(np.shape(leaf), mesh))
                    if multihost:
                        return shard_to_global(leaf, sh)
                    return jax.device_put(jnp.asarray(leaf), sh)

                params0 = jax.tree.map(place_leaf, params0)
        else:
            def place(c):
                return jnp.asarray(c)

        cells_b = [place(c) for c in cells]
    n_padded = cells[0].size

    grid_shape = (len(spec.noise_powers), len(spec.alphas), len(spec.seeds))

    def _shape_flat(a) -> np.ndarray:
        """Fused flat order (A·P·B, T) → the (A, P, Nn, Na, Ns, T) grid
        (A == 1 when the algorithm axis isn't traced)."""
        return np.asarray(a).reshape(
            (len(algs), len(spec.policies)) + grid_shape + (spec.n_rounds,)
        )

    def _shape_stacked(a) -> np.ndarray:
        """Per-policy stack (P, A·B, T) → the (A, P, Nn, Na, Ns, T) grid."""
        shaped = np.asarray(a).reshape(
            (len(spec.policies), len(algs)) + grid_shape + (spec.n_rounds,)
        )
        return np.moveaxis(shaped, 1, 0)

    def one_engine(cfg: POFLConfig):
        return cached_engine(
            loss_fn, data, cfg,
            channel_cfg=channel_cfg,
            scenario=scenario,
            scenario_params=scenario_params,
            eval_fn=eval_fn,
            mesh=mesh,
            obs=obs,
        )

    def _emit_run(eng, warm: bool, tr0: int, co0: int, **fields) -> None:
        """One ``lattice`` JSONL event per engine dispatch — the raw material
        of the ``repro.obs.report`` warm-retrace gate."""
        emit(
            "lattice", "lattice.run",
            cells=n_real, n_rounds=spec.n_rounds, multihost=multihost,
            algorithms=len(algs), warm=warm,
            trace_delta=eng.n_lattice_traces - tr0,
            compile_delta=eng.n_compiles - co0,
            engine_compiles=eng.n_compiles,
            **fields,
        )

    def _grid_eval(ev, shape_fn) -> Any:
        """Reshape the flat model-task eval subtree (tasks.EvalRecord of
        (cells, T) leaves) to (A, P, Nn, Na, Ns, E) curves."""
        return type(ev)(
            *(shape_fn(np.asarray(a))[..., do_eval] for a in ev)
        )

    def _grid_health(h, shape_fn) -> Any:
        """Reshape the flat quarantine subtree (core.metrics.RoundHealth of
        (cells, T) leaves) to the (A, P, Nn, Na, Ns, T) grid."""
        return type(h)(*(shape_fn(np.asarray(a)) for a in h))

    def _grid_diag(tap_arrays, shape_fn) -> Any:
        """Reshape flat tap leaves to the (A, P, Nn, Na, Ns, T) grid."""
        from repro.core.metrics import RoundDiagnostics

        shaped = RoundDiagnostics(*(shape_fn(a) for a in tap_arrays))
        emit(
            "diag", "lattice.diagnostics",
            cells=n_real, n_rounds=spec.n_rounds,
            taps={
                f: np.mean(
                    getattr(shaped, f),
                    axis=tuple(range(getattr(shaped, f).ndim - 1)),
                ).tolist()
                for f in shaped._fields
            },
        )
        return shaped

    if fuse_policies:
        if traced_algs:
            noise_b, alpha_b, seed_b, policy_b, algorithm_b = cells_b
        else:
            noise_b, alpha_b, seed_b, policy_b = cells_b
            algorithm_b = None
        cfg = dataclasses.replace(
            base_cfg, policy=FUSED_POLICY, local_algorithm=base_alg,
            n_devices=data.n_devices,
        )
        eng = one_engine(cfg)
        warm, tr0, co0 = eng.n_lattice_traces > 0, eng.n_lattice_traces, eng.n_compiles
        recs = eng.run_lattice_cells(
            params0, t_ints, do_eval, noise_b, alpha_b, seed_b,
            policy_b=policy_b, algorithm_b=algorithm_b,
        )
        # multi-host: drain the (collective-free) compute before the
        # gather's single collective program launches anywhere —
        # overlapping launches are what the CPU gloo runtime cannot be
        # trusted with
        _wait(recs, multihost)
        with span("lattice.stream_out"):
            # single stream-out: device → host exactly once for the whole
            # lattice, dropping any dead padding cells
            recs = gather_records(recs, mesh) if multihost else jax.device_get(recs)
        _emit_run(eng, warm, tr0, co0, fused=True)
        with span("lattice.assemble"):
            recs = jax.tree.map(lambda a: a[:n_real], recs)

            def gather(field: str, eval_only: bool) -> np.ndarray:
                # (A·P·B, T) flat, algorithm-major then policy-major
                stacked = _shape_flat(getattr(recs, field))
                return stacked[..., do_eval] if eval_only else stacked

            diag = None if recs.diag is None else _grid_diag(list(recs.diag), _shape_flat)
            ev = None if recs.eval is None else _grid_eval(recs.eval, _shape_flat)
            health = (
                None if recs.health is None
                else _grid_health(recs.health, _shape_flat)
            )
            return _assemble_records(
                spec, algs, gather, eval_rounds, diag=diag, eval=ev, health=health
            )

    if traced_algs:
        noise_b, alpha_b, seed_b, algorithm_b = cells_b
    else:
        noise_b, alpha_b, seed_b = cells_b
        algorithm_b = None
    per_policy = []
    for policy in spec.policies:
        # same traced-dispatch cell program, constant policy axis — one
        # (smaller) compile per policy, per-cell values bit-identical to the
        # fused program's lanes
        with span("lattice.place"):
            policy_b = place(
                np.full((n_padded,), scheduling.policy_id(policy), np.int32)
            )
        cfg = dataclasses.replace(
            base_cfg, policy=policy, local_algorithm=base_alg,
            n_devices=data.n_devices,
        )
        eng = one_engine(cfg)
        warm, tr0, co0 = (
            eng.n_lattice_traces > 0, eng.n_lattice_traces, eng.n_compiles
        )
        recs = eng.run_lattice_cells(
            params0, t_ints, do_eval, noise_b, alpha_b, seed_b,
            policy_b=policy_b, algorithm_b=algorithm_b,
        )
        _emit_run(eng, warm, tr0, co0, fused=False, policy=policy)
        if multihost:
            _wait(recs, multihost)
        per_policy.append(recs)  # stays on device until the final stream-out

    _wait(per_policy, multihost)
    # single stream-out: device → host exactly once for the whole lattice,
    # dropping any dead padding cells (multi-host: a tiled allgather first —
    # no process can address the other hosts' record shards directly)
    with span("lattice.stream_out"):
        per_policy = (
            gather_records(per_policy, mesh) if multihost else jax.device_get(per_policy)
        )
    with span("lattice.assemble"):
        per_policy = jax.tree.map(lambda a: a[:n_real], per_policy)

        def gather(field: str, eval_only: bool) -> np.ndarray:
            stacked = np.stack([getattr(r, field) for r in per_policy])  # (P, A·B, T)
            stacked = _shape_stacked(stacked)
            return stacked[..., do_eval] if eval_only else stacked

        diag = None
        if per_policy and per_policy[0].diag is not None:
            diag = _grid_diag([
                np.stack([np.asarray(getattr(r.diag, f)) for r in per_policy])
                for f in per_policy[0].diag._fields
            ], _shape_stacked)
        ev = None
        if per_policy and per_policy[0].eval is not None:
            first_ev = per_policy[0].eval
            ev = _grid_eval(
                type(first_ev)(*(
                    np.stack([np.asarray(getattr(r.eval, f)) for r in per_policy])
                    for f in first_ev._fields
                )),
                _shape_stacked,
            )
        health = None
        if per_policy and per_policy[0].health is not None:
            first_h = per_policy[0].health
            health = type(first_h)(*(
                _shape_stacked(
                    np.stack([np.asarray(getattr(r.health, f)) for r in per_policy])
                )
                for f in first_h._fields
            ))
        return _assemble_records(
            spec, algs, gather, eval_rounds, diag=diag, eval=ev, health=health
        )


def _wait(recs, multihost: bool) -> None:
    """Block until the device has produced ``recs``, inside a
    ``lattice.wait`` span. On one host their copies to the host start first,
    as ``jax.device_get`` alone would start them, so waiting apart from the
    stream-out adds no round trip."""
    with span("lattice.wait"):
        if not multihost:
            for leaf in jax.tree.leaves(recs):
                leaf.copy_to_host_async()
        jax.block_until_ready(recs)


def _concat_algorithms(
    algs: tuple[str, ...], per_alg: list[LatticeRecords]
) -> LatticeRecords:
    """Stitch per-algorithm (1, P, ...) records back into one (A, P, ...)
    lattice — the ``fuse_algorithms=False`` assembly."""
    first = per_alg[0]
    cat = {
        f: np.concatenate([np.asarray(getattr(r, f)) for r in per_alg], axis=0)
        for f in ("e_com", "e_var", "grad_norm", "n_scheduled", "loss", "acc")
    }
    diag = None
    if first.diag is not None:
        diag = type(first.diag)(*(
            np.concatenate([np.asarray(getattr(r.diag, f)) for r in per_alg], axis=0)
            for f in first.diag._fields
        ))
    ev = None
    if first.eval is not None:
        ev = type(first.eval)(*(
            np.concatenate([np.asarray(getattr(r.eval, f)) for r in per_alg], axis=0)
            for f in first.eval._fields
        ))
    health = None
    if first.health is not None:
        health = type(first.health)(*(
            np.concatenate(
                [np.asarray(getattr(r.health, f)) for r in per_alg], axis=0
            )
            for f in first.health._fields
        ))
    return LatticeRecords(
        axes={**first.axes, "algorithm": list(algs)},
        eval_rounds=first.eval_rounds,
        diag=diag,
        eval=ev,
        health=health,
        **cat,
    )


def _assemble_records(
    spec: LatticeSpec, algs, gather, eval_rounds, diag=None, eval=None,
    health=None,
) -> LatticeRecords:
    return LatticeRecords(
        axes={
            "algorithm": list(algs),
            "policy": list(spec.policies),
            "noise_power": list(spec.noise_powers),
            "alpha": list(spec.alphas),
            "seed": list(spec.seeds),
        },
        e_com=gather("e_com", False),
        e_var=gather("e_var", False),
        grad_norm=gather("grad_norm", False),
        n_scheduled=gather("n_scheduled", False),
        loss=gather("loss", True),
        acc=gather("acc", True),
        eval_rounds=eval_rounds,
        diag=diag,
        eval=eval,
        health=health,
    )


def fused_flat_grid(
    spec: LatticeSpec,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray | None]:
    """The policy-fused flattened cell grid of ``spec`` as
    ``(noise, alpha, seed, policy_id, algorithm_id-or-None)`` flat (B,)
    arrays — EXACTLY the fused order ``run_lattice`` vmaps over (algorithm-
    major, then policy-major, then noise × alpha × seed), so a flat index
    reshapes to the (A, P, Nn, Na, Ns) grid with a plain ``reshape``.
    ``algorithm_id`` is ``None`` for single-algorithm specs (the static-
    dispatch path). ``sim.resilience`` shards THIS order across workers.
    """
    for a in spec.algorithms:
        local_update.algorithm_id(a)
    grid_axes = [
        np.asarray(spec.noise_powers, np.float32),
        np.asarray(spec.alphas, np.float32),
        np.asarray(spec.seeds, np.int32),
    ]
    pol_ids = np.asarray(
        [scheduling.policy_id(p) for p in spec.policies], np.int32
    )
    if len(spec.algorithms) > 1:
        alg_ids = np.asarray(
            [local_update.algorithm_id(a) for a in spec.algorithms], np.int32
        )
        grid_al, grid_p, grid_n, grid_a, grid_s = np.meshgrid(
            alg_ids, pol_ids, *grid_axes, indexing="ij"
        )
        return (
            grid_n.ravel(), grid_a.ravel(), grid_s.ravel(),
            grid_p.ravel(), grid_al.ravel(),
        )
    grid_p, grid_n, grid_a, grid_s = np.meshgrid(
        pol_ids, *grid_axes, indexing="ij"
    )
    return grid_n.ravel(), grid_a.ravel(), grid_s.ravel(), grid_p.ravel(), None


def assemble_flat_fused(
    spec: LatticeSpec, flat_records, do_eval: np.ndarray,
    eval_rounds: np.ndarray,
) -> LatticeRecords:
    """Assemble a flat fused-order record pytree into :class:`LatticeRecords`.

    ``flat_records`` is a host-side ``RoundRecord`` whose leaves are
    ``(B, T)`` arrays in :func:`fused_flat_grid` order (B = ``spec.n_cells``)
    — what the chunked engine programs of ``sim.resilience`` accumulate, and
    what a supervisor reassembles from per-worker shards. The reshape (and
    the optional diag/eval/health subtree handling) matches ``run_lattice``'s
    fused path exactly.
    """
    algs = tuple(spec.algorithms)
    grid_shape = (len(spec.noise_powers), len(spec.alphas), len(spec.seeds))

    def shape_flat(a) -> np.ndarray:
        return np.asarray(a).reshape(
            (len(algs), len(spec.policies)) + grid_shape + (spec.n_rounds,)
        )

    def gather(field: str, eval_only: bool) -> np.ndarray:
        stacked = shape_flat(getattr(flat_records, field))
        return stacked[..., do_eval] if eval_only else stacked

    diag = None
    if flat_records.diag is not None:
        diag = type(flat_records.diag)(
            *(shape_flat(a) for a in flat_records.diag)
        )
    ev = None
    if flat_records.eval is not None:
        ev = type(flat_records.eval)(
            *(shape_flat(np.asarray(a))[..., do_eval] for a in flat_records.eval)
        )
    health = None
    if flat_records.health is not None:
        health = type(flat_records.health)(
            *(shape_flat(a) for a in flat_records.health)
        )
    return _assemble_records(
        spec, algs, gather, eval_rounds, diag=diag, eval=ev, health=health
    )
