"""repro.sim — vectorized scenario-lattice simulation engine for PO-FL.

Three layers (see ROADMAP.md "sim" section):

  * :mod:`repro.sim.scenario` — stateful channel processes (static Rayleigh,
    Gauss–Markov fading, mobility, dropout, churn) and data-heterogeneity
    presets (iid / shards / dirichlet / dirichlet_sized / dirichlet_mixed)
    behind string registries.
  * :mod:`repro.sim.engine`   — the ``lax.scan``-over-rounds round engine
    with a donated carry; ``core.pofl.run_pofl`` is a wrapper over it.
  * :mod:`repro.sim.lattice`  — experiment-lattice specs
    (algorithms × policies × noise_powers × alphas × seeds [× n_devices])
    compiled into
    one vmapped+scanned program per (policy, shape) group, optionally
    sharded along the cell axis over a ``jax.sharding`` mesh
    (``run_lattice(..., mesh=...)`` / :func:`make_cell_mesh`).
  * :mod:`repro.sim.tasks`    — real-model federated tasks
    (:func:`make_model_task`: the paper's logreg / 4-conv CNN over synthetic
    MNIST-/CIFAR-shaped data) with pad-masked :class:`TaskEval` evals that
    surface accuracy/loss curves as the ``LatticeRecords.eval`` subtree
    (OFF — an empty pytree — for any other eval_fn).
  * :mod:`repro.sim.multihost` — the process-spanning half of the lattice
    sharding story: ``jax.distributed`` init from the ``REPRO_DIST_*`` env
    contract (:func:`initialize_distributed`), global-device cell meshes
    (:func:`make_global_cell_mesh`), per-process shard feeding and record
    gathering. Driven locally by ``repro.launch.distributed``.
  * :mod:`repro.sim.resilience` — fault tolerance: checkpoint/resume of
    chunked lattice sweeps (:func:`run_lattice_checkpointed` — resume is
    bit-identical to uninterrupted), per-worker shard runs for the
    supervised launcher, and the deterministic ``REPRO_FAULT_*``
    fault-injection contract.
"""
from repro.sim.compile_cache import (
    enable_compile_cache,
    persistent_cache_counters,
)
from repro.sim.engine import (
    FUSED_ALGORITHM,
    FUSED_POLICY,
    SimEngine,
    SimState,
    cached_engine,
    engine_cache_stats,
    latest_lattice_executable,
    lattice_compile_stats,
    lattice_memory_stats,
    reset_engine_cache,
)
from repro.sim.lattice import (
    LatticeRecords,
    LatticeSpec,
    make_cell_mesh,
    make_cell_model_mesh,
    run_lattice,
)
from repro.sim.resilience import (
    CheckpointConfig,
    latest_checkpoint,
    merge_shards,
    run_lattice_checkpointed,
    run_worker_shard,
)
from repro.sim.multihost import (
    DistributedConfig,
    distributed_env,
    initialize_distributed,
    make_global_cell_mesh,
    make_global_cell_model_mesh,
    mesh_spans_processes,
)
from repro.sim.scenario import (
    CHANNEL_SCENARIOS,
    PARTITIONS,
    make_channel_process,
    make_partition,
)
from repro.sim.tasks import (
    TASKS,
    EvalRecord,
    ModelTask,
    TaskEval,
    make_model_task,
)

__all__ = [
    "CHANNEL_SCENARIOS",
    "CheckpointConfig",
    "DistributedConfig",
    "EvalRecord",
    "FUSED_ALGORITHM",
    "FUSED_POLICY",
    "LatticeRecords",
    "LatticeSpec",
    "ModelTask",
    "PARTITIONS",
    "SimEngine",
    "SimState",
    "TASKS",
    "TaskEval",
    "cached_engine",
    "distributed_env",
    "enable_compile_cache",
    "engine_cache_stats",
    "initialize_distributed",
    "latest_lattice_executable",
    "lattice_compile_stats",
    "lattice_memory_stats",
    "latest_checkpoint",
    "make_cell_mesh",
    "make_cell_model_mesh",
    "make_channel_process",
    "make_global_cell_mesh",
    "make_global_cell_model_mesh",
    "make_model_task",
    "make_partition",
    "merge_shards",
    "mesh_spans_processes",
    "persistent_cache_counters",
    "reset_engine_cache",
    "run_lattice",
    "run_lattice_checkpointed",
    "run_worker_shard",
]
