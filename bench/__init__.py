"""The chip benchmark of this repository: one cell per run, driven by
BENCHMARK.json and the files it names (see bench/run.py)."""
