"""Benchmark of the DPLR corpus ranking server (see bench/README.md)."""
