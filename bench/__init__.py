"""On-chip benchmark of the serving path, driven by ``BENCHMARK.json``."""
