"""Chip benchmark of the Redynis simulator (see ``run.py``)."""
