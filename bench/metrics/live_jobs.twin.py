"""Job table: live jobs (queued or running) in the twin's window at each
decision (``CycleRecord.live_jobs``), the mean over the window's
cycles."""
from bench.readings import untraced


def read(record):
    xs = untraced(record, "live_jobs")
    return sum(xs) / len(xs) if xs else None
