"""Scheduling-pass kernel's share of its roofline in the traced grid calls."""
from bench.readings import pass_roofline as read  # noqa: F401
