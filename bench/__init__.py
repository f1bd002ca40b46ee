"""Chip benchmark of the federated round engine (see run.py)."""
