"""The benchmark: cells named in ``BENCHMARK.json``, run one at a time by
``bench/run.py``.  Everything that belongs to one configuration, one job,
one entry point, one generator, one reference, one step's work or one
per-layer metric is a file of its own, found by name (``bench/cells.py``)."""
