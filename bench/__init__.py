"""Chip benchmark of the SSSP engine: one cell per run, driven by data.

See ``run.py`` for the command line and PERF.md for the cells.
"""
