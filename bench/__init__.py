"""Chip benchmark of the FlashBias serve path (see ``bench/run.py``)."""
