"""The benchmark's own directory: harness, yardstick and data (see PERF.md)."""
