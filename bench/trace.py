"""From a profiler trace to device busy time, kernel time and breakdown.

``read(path)`` turns a JAX profiler ``.xplane.pb`` into plain event
lists with ``jax.profiler.ProfileData``; ``reduce_events`` does the
arithmetic on those lists, so tests can feed it known events.

* A device's busy time is the union of its op intervals inside the
  traced window; idle is the rest of the window.
* A kernel's time is the sum of the durations of its op events.
* ``breakdown`` lists the ops that took most device time (self time:
  ops nest, a ``while`` holds its body's ops), and the longest idle
  gaps, each named by the benchmark's innermost host span that covers
  the middle of the gap.

Op events are named as the profiler prints them, cut to the HLO
instruction's name (``%fusion.8``, ``%policy_eval_pass_batched.1``).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Interval = Tuple[int, int]          # [start, end) in ns

#: The host span (``jax.profiler.TraceAnnotation``) around the traced
#: stretch; each driver names its own spans around the calls it makes
#: into the program (``spans``).
WINDOW_SPAN = "bench_window"


class Event(NamedTuple):
    name: str
    start: int      # ns
    dur: int        # ns


class Summary(NamedTuple):
    window_s: float
    busy_s: Dict[str, float]          # per device
    kernel_s: Dict[str, float]        # per device: matched kernel time
    op_s: Dict[str, float]            # op name -> seconds, all devices
    idle_gaps: List[Tuple[str, float]]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def union_ns(intervals: Iterable[Interval]) -> int:
    """Total length covered by the intervals."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps_ns(intervals: Iterable[Interval], lo: int,
            hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Interval]:
    return [(max(e.start, lo), min(e.start + e.dur, hi)) for e in events
            if e.start < hi and e.start + e.dur > lo]


def _label(spans: Sequence[Event], t: int) -> str:
    inner = None
    for s in spans:
        if s.name != WINDOW_SPAN and s.start <= t < s.start + s.dur:
            if inner is None or s.dur < inner.dur:
                inner = s
    return inner.name if inner is not None else "outside benchmark spans"


def self_ns(intervals: Sequence[Interval]) -> List[int]:
    """Each interval's length less what the intervals nested in it
    cover (the intervals of one device line nest or are disjoint)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [b - a for a, b in intervals]
    stack: List[int] = []
    for i in order:
        a, b = intervals[i]
        while stack and intervals[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def reduce_events(device_ops: Dict[str, Sequence[Event]],
                  host_spans: Sequence[Event], kernel: "re.Pattern",
                  top: int = 10) -> Summary:
    """Reduce per-device op events and the benchmark's host spans over
    the window span (``WINDOW_SPAN``)."""
    window = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo = min(s.start for s in window)
    hi = max(s.start + s.dur for s in window)
    busy, kern, ops, gaps = {}, {}, {}, []
    for dev, events in device_ops.items():
        inside = [e for e in events if e.start < hi and e.start + e.dur > lo]
        iv = _clip(inside, lo, hi)
        busy[dev] = union_ns(iv) / 1e9
        kern[dev] = sum(b - a for (a, b), e in zip(iv, inside)
                        if kernel.search(e.name)) / 1e9
        for own, e in zip(self_ns(iv), inside):
            ops[e.name] = ops.get(e.name, 0.0) + own / 1e9
        gaps.extend((_label(host_spans, (a + b) // 2), (b - a) / 1e9)
                    for a, b in gaps_ns(iv, lo, hi))
    gaps.sort(key=lambda g: -g[1])
    top_ops = dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top])
    return Summary((hi - lo) / 1e9, busy, kern, top_ops, gaps[:top])


#: Device planes and the line that holds one event per executed op.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"


def read(path: str, spans: Sequence[str] = ()
         ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device ops by device plane, the host spans named ``spans`` and
    the window span) from one ``.xplane.pb``."""
    names = {WINDOW_SPAN, *spans}
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        Event(e.name.split(" = ")[0], int(e.start_ns),
                              int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns),
                                  int(e.duration_ns))
                            for e in line.events if e.name in names)
    return devices, host


def breakdown(summary: Summary) -> dict:
    return {"device_ops": [[k, v] for k, v in summary.op_s.items()],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
