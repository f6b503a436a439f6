"""The chip benchmark of the synchronous FLoCoRA round: ``bench/run.py``
runs one cell of ``BENCHMARK.json``."""
