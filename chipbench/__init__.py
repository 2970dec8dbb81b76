"""Chip benchmark of the MWD stencil engine; see BENCHMARK.json and run.py."""
