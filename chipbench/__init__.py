"""Chip benchmark of the Quake serving path (see PERF.md)."""
