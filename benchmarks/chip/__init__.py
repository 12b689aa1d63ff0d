"""Chip benchmark of the batched MIND replay (see ``run.py``)."""
