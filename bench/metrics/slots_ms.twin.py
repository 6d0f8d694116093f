"""Job table: median ``twin.slots`` stage of a decision cycle's pump
(the id <-> slot map, slot releases and the packing before the
upload; ``CycleRecord.stages``)."""
from bench.readings import quantile, untraced


def read(record):
    xs = untraced(record, "slots_s")
    return 1e3 * quantile(xs, 0.5) if xs else None
