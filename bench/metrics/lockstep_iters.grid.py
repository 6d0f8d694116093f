"""Event-loop advance: lock-step iterations per grid call."""
from bench.readings import lockstep_iters as read  # noqa: F401
