"""Median served decision latency: the emulator's call into
``SchedTwin.pump`` to its return after ``qrun``, over every pump of the
window that recorded a decision cycle."""
from bench.readings import quantile, untraced


def read(record):
    xs = untraced(record, "pump_s")
    return 1e3 * quantile(xs, 0.5) if xs else None
