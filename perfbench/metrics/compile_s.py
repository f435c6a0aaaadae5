"""Engine: seconds the program spent compiling (or loading from the cache)
its programs during set-up, from its module's ``counters()`` (the
lattice's ``lattice.compile_seconds``). None where it does not count them."""


def read(ctx):
    return ctx.compile_s
