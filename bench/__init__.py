"""Benchmark harness: see ``bench/run.py`` and ``bench/harness.py``."""
