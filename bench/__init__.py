"""On-chip benchmark of the served path: one cell per run, driven by data.

``BENCHMARK.json`` at the repository root names the cells, configurations,
traffic mixes and metrics; each of those lives in a file of its own under
this directory and is found by its name (see ``harness.py``).
"""
