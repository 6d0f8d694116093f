"""Host-emulator regressions: the stale-end sequence guard, the
failure-timeline utilization denominator, and a static run cut at a
submission (``start_at``)."""
import numpy as np
import pytest

from repro.cluster.emulator import ClusterEmulator, FailureSpec
from repro.cluster.workload import JobSpec, poisson_trace
from repro.core.policies import FCFS


def test_stale_end_seq_guard_on_colliding_restart():
    """A killed job restarts so soon that its rescheduled end quantizes
    to the SAME event time as the stale end from its first run.  The
    old float-epsilon guard (`t < end_t - 1e-9`) mis-retired the job at
    the stale event — one heap position early — which reordered the
    same-instant scheduling passes: the full-cluster head job then
    waited behind a long backfill (start 400) instead of starting at
    100.  The sequence guard skips the stale event and retires the job
    at the end event its own restart pushed.
    """
    trace = [
        # restarts at 2e-7 after a transient failure; both its stale end
        # (0 + 100) and its real end (2e-7 + 100) quantize to f32 100.0
        JobSpec(0, 0.0, 16, 300.0, 100.0, "restarted"),
        # actual end also at f32(5e-8 + 100) == 100.0, its end event
        # sits BETWEEN job 0's stale and real end events in the heap
        JobSpec(1, 5e-8, 16, 500.0, 100.0, "between"),
        JobSpec(2, 10.0, 32, 10.0, 10.0, "head"),
        JobSpec(3, 20.0, 4, 300.0, 300.0, "backfill"),
    ]
    failures = [FailureSpec(time=1e-7, nodes=5, duration=1e-7)]
    em = ClusterEmulator(trace, 32, failures=failures,
                         check_invariants=True)
    rep = em.run(policy_id=FCFS)
    assert rep.n_restarts == 1
    # correct order: job 1 retires first (pass sees job 0 still running,
    # shadow 300 blocks the backfill), then job 0's REAL end retires it
    # and the head starts at 100; the backfill follows at 110.
    assert rep.start_t[2] == pytest.approx(100.0)
    assert rep.start_t[3] == pytest.approx(110.0)
    assert rep.end_t[0] == pytest.approx(100.0)


def test_utilization_integrates_failure_timeline():
    """A permanent (duration=0) failure halves the cluster mid-run; the
    utilization denominator must integrate the shrunken capacity, not
    divide by the original ``total_nodes`` for the whole span."""
    trace = [
        JobSpec(0, 0.0, 8, 100.0, 100.0, "a"),    # runs 0..100
        JobSpec(1, 60.0, 8, 100.0, 100.0, "b"),   # waits, runs 100..200
    ]
    failures = [FailureSpec(time=50.0, nodes=8, duration=0.0)]
    em = ClusterEmulator(trace, 16, failures=failures,
                         check_invariants=True)
    rep = em.run(policy_id=FCFS)
    assert rep.n_restarts == 0
    np.testing.assert_allclose(rep.start_t, [0.0, 100.0])
    # node-seconds = 2 * 8 * 100 = 1600; available = 16*50 + 8*150 = 2000
    assert rep.utilization == pytest.approx(1600.0 / 2000.0)
    # the old denominator (total_nodes * makespan = 16 * 200) said 0.5
    assert rep.utilization != pytest.approx(0.5)


def test_utilization_unchanged_without_failures():
    trace = [JobSpec(0, 0.0, 8, 100.0, 100.0, "a"),
             JobSpec(1, 0.0, 8, 50.0, 50.0, "b")]
    rep = ClusterEmulator(trace, 16).run(policy_id=FCFS)
    # node-seconds 8*100 + 8*50 = 1200 over 16 * 100
    assert rep.utilization == pytest.approx(1200.0 / 1600.0)


def test_event_times_are_f32_representable():
    """Ingestion quantizes job fields to f32, so every event time (a
    sum of f32 values in f64) is itself exactly f32-representable —
    the property that keeps host and device replays bit-identical."""
    from repro.cluster.workload import poisson_trace
    trace = poisson_trace(16, 16, 5.3, (1, 12), (31.7, 299.9), seed=11)
    rep = ClusterEmulator(trace, 16).run(policy_id=FCFS)
    for arr in (rep.start_t, rep.end_t, rep.submit_t):
        np.testing.assert_array_equal(arr, arr.astype(np.float32))


@pytest.mark.parametrize("cut", [10, 57, 99, 118])
def test_start_at_is_the_host_loop_cut_at_a_submission(cut):
    """A fresh emulator put by ``start_at`` at a submission of a static
    run holds what the host loop holds there: the same snapshot, heap
    included, and the same rest of the run."""
    from repro.core.engine import DrainEngine
    from repro.core.state import INVALID
    eng = DrainEngine("reference")
    trace = poisson_trace(120, 16, 20.0, (1, 8), (30.0, 600.0), seed=3)
    report = ClusterEmulator(trace, 16, engine=eng).run(policy_id=FCFS,
                                                        fast=True)
    host = ClusterEmulator(trace, 16, engine=eng)
    assert host.advance(lambda: int(np.count_nonzero(host.state != INVALID))
                        > cut, policy_id=FCFS)
    fast = ClusterEmulator(trace, 16, engine=eng)
    fast.start_at(report, cut)
    h, f = host.snapshot_state(), fast.snapshot_state()
    assert sorted(map(tuple, h.pop("heap"))) == \
        sorted(map(tuple, f.pop("heap")))
    assert h == f
    rest = fast.run(policy_id=FCFS)
    np.testing.assert_array_equal(rest.start_t, report.start_t)
    np.testing.assert_array_equal(rest.end_t, report.end_t)


def test_start_at_refuses_an_instant_another_event_shares():
    trace = [JobSpec(0, 0.0, 4, 10.0, 10.0), JobSpec(1, 5.0, 4, 10.0, 10.0),
             JobSpec(2, 5.0, 4, 10.0, 10.0), JobSpec(3, 10.0, 4, 10.0, 10.0)]
    report = ClusterEmulator(trace, 8).run(policy_id=FCFS, fast=True)
    with pytest.raises(ValueError, match="shares"):
        ClusterEmulator(trace, 8).start_at(report, 1)   # job 2 at t=5
    with pytest.raises(ValueError, match="shares"):
        ClusterEmulator(trace, 8).start_at(report, 3)   # job 0 ends at 10
    ClusterEmulator(trace, 8).start_at(report, 2)
