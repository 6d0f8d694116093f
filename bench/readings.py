"""Arithmetic shared by the metric readers in ``bench/metrics``."""
from __future__ import annotations

import math
from typing import Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile: the smallest sample with at least a
    share ``q`` of the samples at or below it."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def untraced(record, key: str) -> list:
    """A twin sample series without the cycles of the traced stretch,
    which the profiler slows."""
    xs = record.samples.get(key, [])
    flags = record.samples.get("traced", [0.0] * len(xs))
    return [x for x, t in zip(xs, flags) if not t]


# Grid readings: the one-chip grid and the fleet record the same
# calls, and their readers share these functions.

def replays_per_s(record):
    """(scenario, policy) forks of every untraced grid call of the
    window, each ended by ``block_until_ready``, over the window."""
    calls = [c for c in record.calls if not c["traced"]]
    if not calls or record.window_s <= 0:
        return None
    return sum(c["forks"] for c in calls) / record.window_s


def device_idle(record):
    """1 - busy/window of the traced stretch, the mean over devices."""
    t = record.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)


def pass_share(record):
    """The pass kernel's device time over device busy time."""
    t = record.trace
    if t is None:
        return None
    busy, kern = sum(t.busy_s.values()), sum(t.kernel_s.values())
    return 100.0 * kern / busy if busy > 0 and kern > 0 else None


def pass_roofline(record):
    """The least time the chip could take for the passes of the traced
    calls (each at the logical (k, J), ``bench/counts.py``) over the
    pass kernel's device time in the trace."""
    from bench import counts, peaks
    t = record.trace
    if t is None or not record.pass_k:
        return None
    kern = sum(t.kernel_s.values())
    passes = sum(c["passes"] for c in record.calls if c["traced"])
    if kern <= 0 or passes <= 0:
        return None
    least, _ = counts.least_seconds(
        counts.pass_work(record.pass_k, record.pass_j),
        peaks.peak(record.device_kind))
    return 100.0 * passes * least / kern


def lockstep_iters(record):
    """``ReplayResult.iters`` per grid call (summed over blocks and
    shards on a fleet), averaged over the calls: an exact count."""
    if not record.calls:
        return None
    return sum(c["iters"] for c in record.calls) / len(record.calls)
