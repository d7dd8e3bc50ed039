"""Chip benchmark of the ABFT-protected serving engine (see PERF.md)."""
