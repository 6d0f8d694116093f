"""The trace reduction on events with known answers (no chip, no trace
file: ``reduce_events`` is the arithmetic ``read`` feeds)."""
import re

import pytest

from bench import trace
from bench.trace import Event

KERNEL = re.compile(r"^%pass")


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union_ns([(0, 10), (5, 15), (15, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0


def test_gaps_are_the_uncovered_stretches_of_the_window():
    assert trace.gaps_ns([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]
    assert trace.gaps_ns([(0, 100)], 0, 100) == []


def test_reduce_busy_kernel_ops_and_labelled_gaps():
    window = Event(trace.WINDOW_SPAN, 1000, 1000)          # [1000, 2000)
    spans = [window, Event("emulator", 1000, 1000),
             Event("pump", 1100, 300), Event("pump", 1600, 300)]
    ops = {"/device:TPU:0": [
        Event("fusion.1", 900, 200),            # clipped to [1000, 1100)
        Event("%pass.3", 1200, 100),
        Event("%pass.3", 1300, 50),      # back to back: union 150
        Event("sort.2", 1700, 100),
        Event("fusion.1", 2500, 10)]}           # outside the window
    s = trace.reduce_events(ops, spans, KERNEL)
    assert s.window_s == pytest.approx(1e-6)
    # busy: [1000,1100) + [1200,1350) + [1700,1800) = 350 ns
    assert s.busy_s["/device:TPU:0"] == pytest.approx(350e-9)
    assert s.kernel_s["/device:TPU:0"] == pytest.approx(150e-9)
    assert s.op_s["%pass.3"] == pytest.approx(150e-9)
    assert s.op_s["fusion.1"] == pytest.approx(100e-9)
    # gaps: [1100,1200) mid 1150 in a pump; [1350,1700) mid 1525 and
    # [1800,2000) mid 1900 (the pump ends at 1900) in emulator time
    assert s.idle_gaps == [("emulator", 350e-9), ("emulator", 200e-9),
                           ("pump", 100e-9)]


def test_busy_averages_over_devices_and_needs_the_window_span():
    spans = [Event(trace.WINDOW_SPAN, 0, 100)]
    ops = {"/device:TPU:0": [Event("a", 0, 100)],
           "/device:TPU:1": [Event("a", 0, 50)]}
    s = trace.reduce_events(ops, spans, KERNEL)
    assert s.mean_busy_s == pytest.approx(75e-9)
    with pytest.raises(ValueError):
        trace.reduce_events(ops, [], KERNEL)


def test_breakdown_is_at_most_ten_of_each():
    spans = [Event(trace.WINDOW_SPAN, 0, 10_000)]
    ops = {"/device:TPU:0": [Event(f"op{i}", 100 * i, 10)
                             for i in range(30)]}
    b = trace.breakdown(trace.reduce_events(ops, spans, KERNEL))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


def test_self_time_subtracts_nested_ops():
    assert trace.self_ns([(0, 100), (10, 30), (40, 90), (50, 60)]) == [
        30, 20, 40, 10]
    spans = [Event(trace.WINDOW_SPAN, 0, 100)]
    ops = {"/device:TPU:0": [Event("%while.1", 0, 100),
                             Event("%pass.2", 10, 60)]}
    s = trace.reduce_events(ops, spans, KERNEL)
    assert s.op_s == {"%pass.2": pytest.approx(60e-9),
                      "%while.1": pytest.approx(40e-9)}
    assert s.kernel_s["/device:TPU:0"] == pytest.approx(60e-9)
    assert s.busy_s["/device:TPU:0"] == pytest.approx(100e-9)
