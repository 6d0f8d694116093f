"""Twin host path: per cycle, the pump span less the engine's decision
(``CycleRecord.wall_seconds``), median over cycles."""
from bench.readings import quantile, untraced


def read(record):
    pump, decide = untraced(record, "pump_s"), untraced(record, "decide_s")
    if not pump:
        return None
    return 1e3 * quantile([p - d for p, d in zip(pump, decide)], 0.5)
