"""Engine: programs compiled inside the measured window, from the program
module's ``counters()`` (the lattice's ``lattice.n_compiles``). Should
read 0; None where the program does not count them."""


def read(ctx):
    return ctx.window_compiles
