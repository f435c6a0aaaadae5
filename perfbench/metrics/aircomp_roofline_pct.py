"""Aggregation kernel: the fused AirComp kernel's share of its HBM
roofline, in %.

The bytes the Eq. 5->8 aggregation needs per cell and round, counted from
shapes at the unpadded D: the (N, D) float32 gradient block and the (D,)
noise read, the (D,) aggregate written. Counted from the work and not
from any implementation, so a later kernel is read against the same
yardstick. One kernel event on a device serves one round of every cell
that device holds. The share is the least time those bytes take at the
chip's HBM bandwidth over the kernel's device time in the trace, averaged
over the chips. The aggregation does no matrix work, so bytes bound it.

``KERNEL`` is the name the kernel's events carry in the trace's ``XLA
Ops`` line: the ``pallas_call`` has no ``name=``, so its custom call takes
the name of the jitted wrapper around it, ``aircomp_fused`` in
``kernels/aircomp/kernel.py`` (events read ``%aircomp_fused.12 = f32[...]
custom-call(...), custom_call_target="tpu_custom_call"`` on a TPU v5e)."""

KERNEL = "aircomp_fused"


def is_kernel(op_name: str) -> bool:
    return KERNEL in op_name


def kernel_ns(device) -> tuple[float, int]:
    """(device time, events) of the kernel on one device."""
    ns = sum(t for n, t in device.op_ns.items() if is_kernel(n))
    count = sum(c for n, c in device.op_count.items() if is_kernel(n))
    return ns, count


def bytes_per_cell_round(config) -> int:
    return (config["n_devices"] + 2) * config["dim"] * 4


def read(ctx):
    from perfbench.peaks import peaks

    if ctx.red is None:
        return None
    bw = peaks(ctx.device_kind)["hbm_bw"]
    cells_per_chip = ctx.n_cells / ctx.chips
    shares = []
    for d in ctx.red.devices:
        ns, count = kernel_ns(d)
        if count == 0:
            return None
        least_s = count * cells_per_chip * bytes_per_cell_round(ctx.config) / bw
        shares.append(100.0 * least_s / (ns / 1e9))
    return sum(shares) / len(shares)
