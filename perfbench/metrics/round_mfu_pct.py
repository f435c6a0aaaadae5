"""Round step: model FLOP utilization of the whole round, in %.

Model FLOPs per cell-round, counted by the cell's program module from the
configuration's shapes (``flops_per_cell_round``; the lattice's in
``perfbench/programs/lattice.py``), times the traced run's cell-rounds per
second, over chips x the chip's bf16 peak. The programs run their
products at the TPU's default precision, one bfloat16 pass, so the bf16
peak is the ceiling. A program that counts no FLOPs reads None."""


def read(ctx):
    from perfbench.peaks import peaks

    flops = ctx.program.flops_per_cell_round(ctx.config, ctx.traffic)
    if flops is None:
        return None
    peak = peaks(ctx.device_kind)["bf16_flops"]
    return 100.0 * flops * ctx.rate / (ctx.chips * peak)
