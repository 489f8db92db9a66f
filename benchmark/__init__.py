"""The yardstick: one cell of BENCHMARK.json, run once, one JSON line.

Entry point: ``python -m benchmark.run`` (see README.md in this directory).
"""
