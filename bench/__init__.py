"""Chip benchmark of the disaggregated serving path (see ``bench/run.py``)."""
