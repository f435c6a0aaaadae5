"""Aggregation kernel: device time of the ``aggregation`` stage per lattice
round, in ms, averaged over the chips: the fused AirComp kernel with its
operand padding and layout, the receiver-noise draw and the closed-form
distortion (``jax.named_scope("aggregation")`` in
``core.pofl.round_algorithm``). Read as ``local_update_ms`` is."""


def read(ctx):
    from perfbench.program_trace import stage_ms

    return stage_ms(ctx, "aggregation")
