"""The serving benchmark: one command, data-driven cells (see PERF.md)."""
