"""Lattice driver: device-idle time per sweep while the host copies the
sweep's records off the device and reshapes them into the grid, in ms,
averaged over the chips: idle time whose innermost program span is
``lattice.stream_out`` or ``lattice.assemble`` (``sim/lattice.py``). Read
as ``dispatch_idle_ms`` is."""


def read(ctx):
    from perfbench.program_trace import idle_ms

    return idle_ms(ctx, ("lattice.stream_out", "lattice.assemble"))
