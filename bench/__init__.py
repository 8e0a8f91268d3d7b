"""Chip benchmark of the federation's ``run_fl`` path (see ``PERF.md``)."""
