"""Chip benchmark of the DP-MD engine: see run.py."""
