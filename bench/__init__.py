"""Chip benchmark of the serving fabric; ``python3 bench/run.py --help``."""
