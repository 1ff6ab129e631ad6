"""The benchmark: one harness, driven by ``BENCHMARK.json`` and data files."""
