"""Forks replayed per second in a grid cell (``bench/readings.py``)."""
from bench.readings import replays_per_s as read  # noqa: F401
