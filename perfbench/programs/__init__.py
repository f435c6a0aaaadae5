"""One module per program a cell can drive, found by the name under the
configuration's ``program`` key (``lattice`` where there is none). The
interface a module supplies is described in ``perfbench/run.py``."""
