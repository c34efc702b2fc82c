"""The chip benchmark: cells, configurations, traffic mixes and metric
readers named in ``BENCHMARK.json``, found here by name (``run.py``)."""
