"""The chip benchmark: one cell of BENCHMARK.json per process (run.py)."""
