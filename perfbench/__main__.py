"""``python3 -m perfbench``: see ``perfbench/run.py``."""
import time

T0 = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402

from perfbench.run import main  # noqa: E402

sys.exit(main(t0=T0))
