"""Chip benchmark of the Valet serve engine: one cell per run, driven by
``BENCHMARK.json`` and the configuration, traffic and metric files found by
name under this directory."""
