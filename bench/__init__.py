"""Benchmark of the TT serving path on the chip: harness, traffic,
configurations, metric readers and the plain reference."""
