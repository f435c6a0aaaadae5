"""Roofline of the sim's hot path: the fused Eq. 5→8 ``aircomp`` kernel,
read off the lattice executable's own XLA ``cost_analysis``.

    compute = HLO_FLOPs / peak_FLOP/s
    memory  = HLO_bytes / HBM_bw

The peaks come from :data:`PEAKS`, keyed by the ``device_kind`` JAX reports
for the device the lattice compiled for. A device that is not in the table
is an error, never a default: a CPU run has no roofline.
"""
from __future__ import annotations

# device_kind -> published per-chip peaks. Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def aircomp_roofline(mesh=None) -> dict:
    """Roofline terms for the sim's REAL hot path: compile a small fused
    (``pallas_fused``) lattice sweep and read XLA's
    ``cost_analysis``/``memory_analysis`` off its AOT executable
    (``repro.sim.latest_lattice_executable``).

    The fused aircomp kernel is one HBM pass over the (cells, N, D) gradient
    block with no MXU work, so its binding term is expected to be
    ``memory_s``; the row reports whichever bound is larger.
    """
    import jax

    from benchmarks.common import bench_task, run_policies
    from repro.sim import latest_lattice_executable

    kind = jax.devices()[0].device_kind
    peaks = device_peaks(kind)
    task = bench_task()
    run_policies(
        task, policies=("pofl",), n_rounds=5, n_trials=2,
        backend="pallas_fused", mesh=mesh,
    )
    exe = latest_lattice_executable()
    cost = exe.cost_analysis()
    mem = exe.memory_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    compute_s = flops / peaks["flops"]
    memory_s = bytes_acc / peaks["hbm_bw"]
    return {
        "device_kind": kind,
        "flops": flops,
        "bytes_accessed": bytes_acc,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "dominant": "memory" if memory_s >= compute_s else "compute",
        "per_device_hbm_bytes": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
        ),
    }


def main() -> dict:
    air = aircomp_roofline()
    print(f"\n== Roofline: sim hot path (fused aircomp lattice) on {air['device_kind']} ==")
    print(
        f"{'kernel':>22s} {'compute_s':>12s} {'memory_s':>12s} "
        f"{'bound':>8s} {'MiB/dev':>8s}"
    )
    print(
        f"{'aircomp_fused':>22s} {air['compute_s']:12.3e} "
        f"{air['memory_s']:12.3e} {air['dominant']:>8s} "
        f"{air['per_device_hbm_bytes']/2**20:8.2f}"
    )
    return air


if __name__ == "__main__":
    main()
