"""Chip benchmark of the PIM-malloc fleet: `python3 bench/run.py --help`."""
