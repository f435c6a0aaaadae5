"""Multi-host lattice plumbing: ``jax.distributed`` init + process-spanning
cell meshes + per-process shard feeding and record gathering.

PR 3 sharded the lattice's flattened cell axis over a *single-process* mesh;
this module is the process-spanning half of that story (and since the PR-5
policy-fused lattice the sharded cell axis spans POLICIES too — the whole
multi-policy spec is one program whose shard feed and record gather route
through here unchanged). Each participating process runs the SAME
``run_lattice`` call (SPMD — every process executes every compiled
dispatch), but only materializes / computes the shard of the padded cell
grid that lives on its addressable devices:

  * :func:`initialize_distributed` wires ``jax.distributed`` from explicit
    args or the ``REPRO_DIST_*`` env contract written by
    ``repro.launch.distributed`` (the local CPU launcher). On CPU it selects
    the ``gloo`` cross-process collectives implementation — the default
    (``none``) cannot run multiprocess computations at all.
  * :func:`make_global_cell_mesh` builds the 1-D ``("cells",)`` mesh over the
    GLOBAL device list (``jax.devices()`` spans every process after
    ``jax.distributed.initialize``); :func:`make_cell_mesh` stays the
    local-devices-only spelling.
  * :func:`shard_to_global` assembles a global ``jax.Array`` from the host
    copy of a cell-axis input: every process holds the full (deterministic)
    numpy grid, slices out its addressable shards via
    ``Sharding.addressable_devices_indices_map``, and stitches them with
    ``jax.make_array_from_single_device_arrays``.
  * :func:`gather_records` brings a pytree of cell-sharded outputs back to
    EVERY host as plain numpy through ONE replicating identity program (a
    single cross-process collective rendezvous per gather), so
    unpadding/reshaping stays ordinary host code and each host — host 0
    included, which is the one that persists results — returns identical
    :class:`~repro.sim.lattice.LatticeRecords`.

None of this touches jax device state at import time: ``initialize_distributed``
must run before the first backend query, so this module is import-safe from
anywhere (the launcher imports it before deciding whether to initialize).
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import Any

import jax
import numpy as np

from repro.obs.spans import span
from repro.sim.engine import _mesh_key

ENV_COORDINATOR = "REPRO_DIST_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_DIST_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_DIST_PROCESS_ID"


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    """One process's view of the ``jax.distributed`` topology."""

    coordinator: str   # "host:port" of process 0's coordination service
    num_processes: int
    process_id: int


def distributed_env() -> DistributedConfig | None:
    """Read the ``REPRO_DIST_*`` env contract; ``None`` when not set.

    The contract is written by ``repro.launch.distributed`` for every worker
    it spawns; real multi-host deployments (SLURM, k8s) can export the same
    three variables instead of passing explicit args.
    """
    names = (ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID)
    values = [os.environ.get(n) for n in names]
    if not any(values):
        return None
    missing = [n for n, v in zip(names, values) if not v]
    if missing:
        raise ValueError(
            f"partial REPRO_DIST_* env contract: missing {missing}; a "
            f"distributed worker must export all of {list(names)}"
        )
    return DistributedConfig(
        coordinator=values[0],
        num_processes=int(values[1]),
        process_id=int(values[2]),
    )


_INITIALIZED = False


def initialize_distributed(cfg: DistributedConfig | None = None) -> bool:
    """Initialize ``jax.distributed`` from ``cfg`` or the env contract.

    Idempotent; a no-op (returning False) when neither names a multi-process
    topology — so single-process callers can call it unconditionally. Must
    run before the first jax backend query (device counts lock at backend
    init). Returns True when this process is part of a multi-process run.

    On CPU the cross-process collective implementation defaults to ``none``,
    which raises "Multiprocess computations aren't implemented on the CPU
    backend" at dispatch — so we switch it to ``gloo`` (shipped in jaxlib)
    before the backend exists.
    """
    global _INITIALIZED
    cfg = cfg or distributed_env()
    if cfg is None or cfg.num_processes <= 1:
        return _INITIALIZED
    if not _INITIALIZED:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # bound the barrier wait: a half-formed topology (a peer crashed
        # before joining) must die loudly, not hang the worker forever
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
            initialization_timeout=120,
        )
        _INITIALIZED = True
    return True


def cells_mesh_over(devices, n_devices: int | None, hint: str) -> jax.sharding.Mesh:
    """Shared constructor behind ``make_cell_mesh`` (local devices) and
    :func:`make_global_cell_mesh` (global devices): validate the count and
    build the 1-D ``("cells",)`` mesh. ``hint`` finishes the error message
    with the scope-appropriate remedy."""
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"mesh wants {n} devices but only {len(devices)} are visible {hint}"
        )
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("cells",))


def make_global_cell_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """A 1-D ``("cells",)`` mesh over the first ``n_devices`` GLOBAL devices.

    After ``initialize_distributed`` the global device list spans every
    process, so the returned mesh does too; in a single-process run this is
    exactly ``make_cell_mesh``. ``None`` takes every global device.
    """
    return cells_mesh_over(
        jax.devices(), n_devices,
        hint=f"across {jax.process_count()} process(es)",
    )


def cell_model_mesh_over(
    devices, cells: int | None, model: int, hint: str
) -> jax.sharding.Mesh:
    """Shared constructor behind the 2-D ``("cells", "model")`` meshes
    (``sim.lattice.make_cell_model_mesh`` over local devices,
    :func:`make_global_cell_model_mesh` over global ones): validate counts
    and reshape the flat device list cells-major, so the first ``model``
    devices form cell-shard 0 — under ``jax.distributed`` a cell's model
    group stays within one process whenever ``model`` divides the per-process
    device count. ``cells=None`` takes every full group of ``model``
    devices."""
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if cells is None:
        cells = len(devices) // model
    n = cells * model
    if not (1 <= cells and 1 <= n <= len(devices)):
        raise ValueError(
            f"mesh wants {cells}x{model} = {n} devices but only "
            f"{len(devices)} are visible {hint}"
        )
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(cells, model), ("cells", "model")
    )


def make_global_cell_model_mesh(
    cells: int | None = None, model: int = 1
) -> jax.sharding.Mesh:
    """A 2-D ``("cells", "model")`` mesh over the GLOBAL device list — the
    process-spanning counterpart of ``sim.lattice.make_cell_model_mesh``."""
    return cell_model_mesh_over(
        jax.devices(), cells, model,
        hint=f"across {jax.process_count()} process(es)",
    )


def mesh_process_span(mesh) -> tuple[int, ...]:
    """Sorted process indices whose devices participate in ``mesh``."""
    return tuple(sorted({d.process_index for d in np.ravel(np.asarray(mesh.devices))}))


def mesh_spans_processes(mesh) -> bool:
    """True when ``mesh`` holds devices from more than one process."""
    return mesh is not None and len(mesh_process_span(mesh)) > 1


def shard_to_global(host_arr, sharding: jax.sharding.NamedSharding) -> jax.Array:
    """Assemble a global array from this process's addressable shards.

    Every process passes the SAME full host array (the cell grids are built
    deterministically from the spec on every host); each only ``device_put``s
    the slices its own devices own, and
    ``jax.make_array_from_single_device_arrays`` stitches them into one
    global array with ``sharding``. Works unchanged in a single process
    (where it is just a sliced ``device_put``).
    """
    host_arr = np.asarray(host_arr)
    index_map = sharding.addressable_devices_indices_map(host_arr.shape)
    shards = [
        jax.device_put(host_arr[index], device)
        for device, index in index_map.items()
    ]
    return jax.make_array_from_single_device_arrays(
        host_arr.shape, sharding, shards
    )


# bounded LRU, same rationale as the engine cache: entries pin mesh/device
# state and a compiled executable, so unbounded growth across successive
# distinct meshes would leak both
_GATHER_JITS: "OrderedDict[tuple, Any]" = OrderedDict()
_GATHER_JITS_MAX = 8


def _identity(leaves):
    return leaves


def gather_records(tree, mesh=None):
    """Gather a pytree of cell-sharded global arrays to EVERY host as numpy.

    Multi-process gathers replicate ALL leaves through ONE jitted identity
    program whose ``out_shardings`` are fully replicated over ``mesh`` — a
    single cross-process rendezvous per gather. (One collective launch per
    leaf — the ``multihost_utils.process_allgather`` spelling — proved racy
    on the CPU gloo runtime: back-to-back collective programs intermittently
    interleaved across processes, corrupting record buffers or deadlocking.)
    The leaves are drained with ``block_until_ready`` first, so no compute
    dispatch is still in flight anywhere when the collective starts. All
    hosts return identical values — host 0 is merely the one expected to
    persist them. Single-process: a plain ``device_get``.
    """
    if jax.process_count() == 1:
        return jax.device_get(tree)
    if mesh is None:
        raise ValueError("multi-process gather_records requires the cell mesh")
    from jax.sharding import NamedSharding, PartitionSpec

    leaves, treedef = jax.tree.flatten(tree)
    jax.block_until_ready(leaves)
    key = (_mesh_key(mesh), len(leaves))
    gather = _GATHER_JITS.get(key)
    if gather is None:
        gather = _GATHER_JITS[key] = jax.jit(
            _identity,
            out_shardings=[NamedSharding(mesh, PartitionSpec())] * len(leaves),
        )
        while len(_GATHER_JITS) > _GATHER_JITS_MAX:
            _GATHER_JITS.popitem(last=False)
    else:
        _GATHER_JITS.move_to_end(key)
    with span("multihost.gather", leaves=len(leaves)):
        gathered = jax.block_until_ready(gather(leaves))
    return jax.tree.unflatten(
        treedef, [np.asarray(g.addressable_data(0)) for g in gathered]
    )
