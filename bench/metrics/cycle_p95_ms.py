"""95th percentile of the served decision latency, over all cycles of
the window."""
from bench.readings import quantile, untraced


def read(record):
    xs = untraced(record, "pump_s")
    return 1e3 * quantile(xs, 0.95) if xs else None
