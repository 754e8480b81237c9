"""The chip benchmark of the served search path (see ``BENCHMARK.json`` at
the repository's root and ``bench/run.py``)."""
