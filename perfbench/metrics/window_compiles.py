"""Engine: lattice programs compiled inside the measured window, from the
program's ``lattice.n_compiles`` counter. Should read 0."""


def read(ctx):
    return ctx.window_compiles
