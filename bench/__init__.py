"""On-chip benchmark of the planner: see BENCHMARK.json and PERF.md."""
