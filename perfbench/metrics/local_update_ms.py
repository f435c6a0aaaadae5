"""Round step: device time of the ``local_update`` stage per lattice round,
in ms, averaged over the chips: the mini-batch gather, the per-device
gradients and their flatten (``jax.named_scope("local_update")`` in
``core.pofl.round_algorithm``). Read from the traced run's ops through the
op -> stage map the program registered at compile time
(``perfbench/program_trace.py``); a lattice round is one step of the scan
over every cell the chip holds."""


def read(ctx):
    from perfbench.program_trace import stage_ms

    return stage_ms(ctx, "local_update")
