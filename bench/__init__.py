"""The chip benchmark: cells named in ``BENCHMARK.json``, run by
``bench/run_cell.py``."""
