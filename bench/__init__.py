"""Chip benchmark of the repository: ``python bench/run.py --help``."""
