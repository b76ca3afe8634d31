"""Benchmark of the design-space explorer on the chip: ``python3 -m
bench.run`` (see ``bench/run.py``)."""
