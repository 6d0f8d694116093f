"""Scheduling-pass kernel's share of device busy time in the traced grid calls."""
from bench.readings import pass_share as read  # noqa: F401
