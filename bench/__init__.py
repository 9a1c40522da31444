"""On-chip benchmark of the Cost-TrustFL round engine (see BENCHMARK.json)."""
