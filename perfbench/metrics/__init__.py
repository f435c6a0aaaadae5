"""One reader per per-layer metric, found by the metric's name.

``perfbench/metrics/<name>.py`` defines ``read(ctx)``, which returns the
metric's value, or None when there is nothing to read (the metric is then
left out of the result line). ``ctx`` carries: ``red``, the trace
reduction of ``perfbench/trace.py`` (None when the trace held nothing);
``config`` and ``traffic``, the cell's files; ``chips``; ``n_cells``;
``rate``, the traced run's cell-rounds per second; ``compile_s`` and
``window_compiles``, the program's compile counters over set-up and over
the window (None where it does not count them); ``device_kind``;
``program``, the cell's program module (``perfbench/run.py``).

A metric without a ``workloads`` list in ``BENCHMARK.json`` is read in
every cell, whatever its program: its reader takes only what every program
supplies, and returns None rather than raising where there is nothing to
read.
"""
