"""Chip benchmark of the trust-aware routed pipeline (see run.py)."""
