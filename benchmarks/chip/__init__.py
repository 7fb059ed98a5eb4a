"""On-chip benchmark of the paged serving engine.

Driven by data: ``BENCHMARK.json`` at the repository root names the
cells, and each configuration, traffic mix, cell and per-layer metric is
a file of its own under this directory, found by its name.  See
``run.py`` for the command.
"""
