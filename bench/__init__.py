"""Chip benchmark of the Radic determinant service (see ``run.py``)."""
