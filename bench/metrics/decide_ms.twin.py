"""Decision engine: median ``CycleRecord.wall_seconds`` (the decide call
and the D2H of its run mask)."""
from bench.readings import quantile, untraced


def read(record):
    xs = untraced(record, "decide_s")
    return 1e3 * quantile(xs, 0.5) if xs else None
