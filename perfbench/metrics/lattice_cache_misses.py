"""Engine: lattice compiles that missed JAX's persistent compilation cache,
from the program's ``lattice.cache_misses`` counter (``sim/engine.py``)
at the end of the run. No lattice program compiles after set-up, so it
says whether set-up compiled the program (1) or loaded it (0)."""


def read(ctx):
    from repro.obs.registry import metrics_snapshot

    return metrics_snapshot("lattice.cache_misses").get("lattice.cache_misses")
