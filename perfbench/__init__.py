"""On-chip benchmark of the PO-FL lattice. Run ``python3 -m perfbench``."""
