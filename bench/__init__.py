"""Chip benchmark of the index rebuild and lookup paths (see ``run.py``)."""
