"""Round step: device time of the ``statistics`` stage per lattice round,
in ms, averaged over the chips: the per-device scalar statistics M_i, V_i,
||g_i|| of the gradient block (``jax.named_scope("statistics")`` in
``core.pofl.round_algorithm``). Read as ``local_update_ms`` is."""


def read(ctx):
    from perfbench.program_trace import stage_ms

    return stage_ms(ctx, "statistics")
