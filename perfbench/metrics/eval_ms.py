"""Round step: device time of the ``eval`` stage per lattice round, in ms,
averaged over the chips: the test-set evaluation on the rounds that
evaluate, spread over every round (``jax.named_scope("eval")`` in
``sim.engine.SimEngine.scan_rounds``). Read as ``local_update_ms`` is."""


def read(ctx):
    from perfbench.program_trace import stage_ms

    return stage_ms(ctx, "eval")
