"""Decision engine: 95th percentile of ``CycleRecord.wall_seconds``."""
from bench.readings import quantile, untraced


def read(record):
    xs = untraced(record, "decide_s")
    return 1e3 * quantile(xs, 0.95) if xs else None
