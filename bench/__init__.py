"""Chip benchmark of the IALS trainer and policy server (see run.py)."""
