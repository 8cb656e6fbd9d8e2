"""Chip benchmark of MDTP's restore-to-HBM path (``run.py`` is the
command; ``BENCHMARK.json`` at the repository root names its cells)."""
