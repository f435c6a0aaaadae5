"""Engine: seconds the program spent compiling (or loading from the cache)
its lattice programs during set-up, from its ``lattice.compile_seconds``
counter."""


def read(ctx):
    return ctx.compile_s
