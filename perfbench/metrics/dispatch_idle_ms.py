"""Lattice driver: device-idle time per sweep while the host places the
sweep's cell axes and dispatches its program, in ms, averaged over the
chips: idle time whose innermost program span is ``lattice.place`` or
``lattice.dispatch`` (``sim/lattice.py``, ``sim/engine.py``). Each idle
interval of the traced window is cut at the span boundaries inside it
(``perfbench/program_trace.py``)."""


def read(ctx):
    from perfbench.program_trace import idle_ms

    return idle_ms(ctx, ("lattice.place", "lattice.dispatch"))
