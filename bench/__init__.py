"""On-chip benchmark of the sweep engine (see ``BENCHMARK.json``)."""
