"""On-chip benchmark of the collaborative serving path (``BENCHMARK.json``)."""
