"""On-chip benchmark of the CARMEN serving path (see ``BENCHMARK.json``)."""
