"""What a run measured, handed from the driver to the metric readers."""
from __future__ import annotations

import contextlib
from typing import Dict, List


def span(name: str, traced: bool):
    """A host span in the profiler's trace while tracing, else nothing."""
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Record:
    """Filled by the harness (set-up, window, trace, device) and by the
    driver's steps (samples, calls, attempts); read by
    ``bench/metrics``.  A reader finds in it only what the cell's driver
    records, and returns nothing where it finds nothing."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_s = 0.0
        self.judge_s = 0.0
        self.judged: Dict[str, float] = {}   # the reference check's counts
        self.samples: Dict[str, List[float]] = {}
        self.calls: List[dict] = []
        self.trace = None              # trace.Summary of a traced run
        self.device_kind = ""
        self.n_devices = 0
        self.pass_k = 0                # forks per pass on one device
        self.pass_j = 0
        self.attempted = 0
        self.failed = 0

    def add(self, key: str, value: float) -> None:
        self.samples.setdefault(key, []).append(value)
