"""On-chip serving benchmark for the paged TCEC serving engine.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a
file of its own under this directory, found by its name.
"""
