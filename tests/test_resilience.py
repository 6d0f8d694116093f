"""Resilient twin runtime (DESIGN.md §12).

Pins the hardened-ingestion, deadline-ladder, and crash-safety
contracts:

- ``EventBus.publish`` isolates subscriber exceptions from the
  producer (and ``health()`` surfaces them);
- malformed events are quarantined into the dead-letter queue, never
  raised mid-cycle;
- ``SeqTracker`` classifies duplicates / reordering / gaps / loss in
  bounded memory, and idempotent ``apply_event`` makes ANY cross-job
  interleaving that preserves per-job lifecycle order (plus arbitrary
  re-delivery) converge to the same mirror (hypothesis property);
- lost events trigger the probe resync and the co-simulation still
  completes every job;
- ``read_with_retry`` backs off exponentially and re-raises after
  exhaustion;
- the deadline guard's degradation ladder is DETERMINISTIC under an
  injected clock (same latencies -> same level trajectory);
- a mid-run ``snapshot()`` + ``restore()`` into a FRESH twin
  reproduces the uninterrupted decision sequence bitwise on BOTH pass
  backends;
- ``ChaosBus`` injections are pure functions of (seed, event seq) —
  the same stream corrupts identically twice.
"""
import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.cluster.chaos import DEFAULT_PROFILE, ChaosBus, ChaosSpec
from repro.cluster.emulator import ClusterEmulator
from repro.cluster.workload import JobSpec
from repro.core.engine import DrainEngine
from repro.core.events import (BusReadError, Event, EventBus, EventKind,
                               SeqTracker, read_with_retry,
                               validate_event)
from repro.core.guard import (LEVEL_NAMES, DeadlineGuard, GuardSpec)
from repro.core.state import DONE, QUEUED, empty_state
from repro.core.sync import apply_event
from repro.core.twin import SchedTwin


def tiny_trace(n=12, seed=0):
    rng = np.random.default_rng(seed)
    jobs, t = [], 0.0
    for j in range(n):
        jobs.append(JobSpec(job_id=j, submit_t=t,
                            nodes=int(rng.integers(1, 6)),
                            est_runtime=float(rng.uniform(20, 80)),
                            true_runtime=float(rng.uniform(10, 80))))
        t += 4.0
    return jobs


def build_cosim(trace, total_nodes=16, view_wrap=None, **twin_kw):
    bus = EventBus()
    em = ClusterEmulator(trace, total_nodes, bus=bus)
    view = view_wrap(bus) if view_wrap else bus
    twin = SchedTwin(bus=view, qrun=em.qrun, total_nodes=total_nodes,
                     max_jobs=em.max_jobs,
                     free_nodes_probe=lambda: em.free_nodes,
                     jobs_probe=em.jobs_view,
                     sleep=lambda s: None, **twin_kw)
    return bus, em, view, twin


def decisions(twin):
    return [(float(c.time), c.policy,
             tuple(int(j) for j in c.started_jobs))
            for c in twin.telemetry.cycles]


# ----------------------------------------------------------------------
# subscriber isolation + bus health
# ----------------------------------------------------------------------

def test_publish_isolates_subscriber_exceptions():
    bus = EventBus()
    seen = []
    bus.subscribe(lambda ev: (_ for _ in ()).throw(RuntimeError("boom")))
    bus.subscribe(seen.append)
    ev = Event(EventKind.QUEUEJOB, 1.0, 0,
               {"nodes": 1.0, "est_runtime": 10.0})
    out = bus.publish(ev)           # must NOT raise into the producer
    assert out.seq == 0
    assert len(bus) == 1            # the event reached the log anyway
    assert len(seen) == 1           # later subscribers still ran
    h = bus.health()
    assert h["callback_failures"] == 1
    assert "boom" in h["last_callback_error"]
    assert h["events"] == 1


def test_bus_dump_round_trip():
    bus = EventBus()
    for j in range(3):
        bus.publish(Event(EventKind.QUEUEJOB, float(j), j,
                          {"nodes": 1.0, "est_runtime": 5.0}))
    clone = EventBus.from_dump(bus.dump())
    assert [e.seq for e in clone.replay()] == [0, 1, 2]
    # the clone's seq counter continues where the log ended
    nxt = clone.publish(Event(EventKind.JOBOBIT, 9.0, 0))
    assert nxt.seq == 3


# ----------------------------------------------------------------------
# malformed-event quarantine (dead-letter queue)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("ev,reason", [
    (Event(99, 1.0, 0), "kind"),
    (Event(EventKind.QUEUEJOB, float("nan"), 0,
           {"nodes": 1.0, "est_runtime": 5.0}), "time"),
    (Event(EventKind.RUNJOB, 1.0, -1), "job"),
    (Event(EventKind.QUEUEJOB, 1.0, 0, {"est_runtime": 5.0}), "nodes"),
    (Event(EventKind.QUEUEJOB, 1.0, 0,
           {"nodes": 0.0, "est_runtime": 5.0}), "nodes"),
    (Event(EventKind.QUEUEJOB, 1.0, 0, {"nodes": 1.0}), "est_runtime"),
    (Event(EventKind.NODEFAIL, 1.0, -1, {}), "nodes"),
    (Event(EventKind.QUEUEJOB, 1.0, 0,
           {"nodes": float("inf"), "est_runtime": 5.0}), "nodes"),
])
def test_validate_event_rejects(ev, reason):
    err = validate_event(ev, max_jobs=8)
    assert err is not None and reason in err


def test_validate_event_accepts_emulator_shapes():
    ok = [Event(EventKind.QUEUEJOB, 0.0, 0,
                {"nodes": 2.0, "est_runtime": 30.0}),
          Event(EventKind.RUNJOB, 1.0, 0),
          Event(EventKind.JOBOBIT, 2.0, 0),
          Event(EventKind.NODEFAIL, 3.0, -1,
                {"nodes": 0.0, "victim_job": 0.0}),
          Event(EventKind.NODEUP, 4.0, -1, {"nodes": 4.0})]
    for ev in ok:
        assert validate_event(ev, max_jobs=8) is None, ev


def test_twin_quarantines_instead_of_crashing():
    trace = tiny_trace(6)
    bus, em, _, twin = build_cosim(trace)
    # a poisoned producer: every real event is followed by garbage
    real_publish = bus.publish

    def poisoned(ev):
        out = real_publish(ev)
        real_publish(Event(EventKind.QUEUEJOB, -5.0, 10 ** 6, {}))
        return out

    bus.publish = poisoned
    report = em.run(on_event=twin.pump, on_quiesce=twin.flush)
    assert report.n_jobs == len(trace)
    assert len(twin.dead_letters) > 0
    assert twin.telemetry.ingest.quarantined == len(twin.dead_letters)
    assert all(dl.reason for dl in twin.dead_letters)


# ----------------------------------------------------------------------
# SeqTracker classification
# ----------------------------------------------------------------------

def test_seqtracker_classifies_and_ages():
    t = SeqTracker(reorder_window=4)
    assert t.observe(0).status == "new"
    assert t.observe(0).status == "duplicate"
    obs = t.observe(3)              # skips 1, 2
    assert obs.status == "new" and obs.new_gaps == 2
    assert t.observe(2).status == "reordered"   # fills a hole
    assert t.observe(2).status == "duplicate"   # already filled
    obs = t.observe(9)              # opens holes 4..8; 1 and 4 age out
    assert obs.new_gaps == 5
    assert obs.newly_lost == 2 and t.lost == {1, 4}
    assert t.observe(1).status == "duplicate"   # lost => late dup
    t2 = SeqTracker.from_dict(t.to_dict())
    assert (t2.max_seen, t2.holes, t2.lost) == (t.max_seen, t.holes,
                                                t.lost)


def test_seqtracker_flush_declares_pending_holes_lost():
    t = SeqTracker(reorder_window=64)
    t.observe(0)
    t.observe(5)                    # holes 1..4 pending, well in window
    assert t.flush() == 4
    assert t.holes == set() and t.lost == {1, 2, 3, 4}


# ----------------------------------------------------------------------
# read_with_retry backoff
# ----------------------------------------------------------------------

def test_read_with_retry_backs_off_and_recovers():
    class Flaky:
        def __init__(self, fail_n):
            self.fail_n, self.calls = fail_n, 0

        def read(self, consumer, max_events=None):
            self.calls += 1
            if self.calls <= self.fail_n:
                raise BusReadError("blip")
            return ["ok"]

    slept, retried = [], []
    out = read_with_retry(Flaky(2), "c", retries=3, backoff_s=0.01,
                          sleep=slept.append,
                          on_retry=lambda a, e: retried.append(a))
    assert out == ["ok"]
    assert slept == [0.01, 0.02]            # exponential
    assert retried == [0, 1]

    with pytest.raises(BusReadError):
        read_with_retry(Flaky(10), "c", retries=2, backoff_s=0.01,
                        sleep=slept.append)


# ----------------------------------------------------------------------
# idempotent apply: interleaving + re-delivery invariance (hypothesis)
# ----------------------------------------------------------------------

def _lifecycle(j):
    """The 3-event lifecycle of job j (valid per validate_event)."""
    t0 = float(j)
    return [Event(EventKind.QUEUEJOB, t0, j,
                  {"nodes": 1.0 + j % 3, "est_runtime": 30.0}),
            Event(EventKind.RUNJOB, t0 + 10.0, j),
            Event(EventKind.JOBOBIT, t0 + 40.0 + j, j)]


def _apply_all(events, n_jobs, nodes=16):
    state = empty_state(8, nodes, xp=np)
    for ev in events:
        state, _ = apply_event(state, ev, idempotent=True)
    return state


def _delivery(order, dup_at, n_jobs=4):
    """``(clean, shuffled)``: the lifecycles of ``n_jobs`` jobs in order,
    and interleaved per ``order`` then re-delivered per ``dup_at``."""
    per_job = [_lifecycle(j) for j in range(n_jobs)]
    clean = [ev for life in per_job for ev in life]
    cursors = [0] * n_jobs
    shuffled = []
    for j in order:                 # per-job order preserved by cursors
        shuffled.append(per_job[j][cursors[j]])
        cursors[j] += 1
    for i in sorted(dup_at):        # arbitrary re-delivery at the tail
        shuffled.append(shuffled[i])
    return clean, shuffled


def _check_invariant(order, dup_at, n_jobs=4):
    """Interleave + re-deliver per ``order``/``dup_at``; final mirror
    must match the clean in-order apply field-for-field."""
    clean, shuffled = _delivery(order, dup_at, n_jobs)
    ref = _apply_all(clean, n_jobs)
    got = _apply_all(shuffled, n_jobs)
    for field in ("submit_t", "nodes", "est_runtime", "start_t",
                  "end_t", "state"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref.jobs, field)),
            np.asarray(getattr(got.jobs, field)), err_msg=field)
    assert int(ref.free_nodes) == int(got.free_nodes)


def test_interleaving_and_redelivery_invariant_mirror_seeded():
    n_jobs = 4
    rng = np.random.default_rng(0)
    tags = np.array([j for j in range(n_jobs) for _ in range(3)])
    for _ in range(50):
        order = rng.permutation(tags)
        dup_at = rng.integers(0, len(tags),
                              size=int(rng.integers(0, 7))).tolist()
        _check_invariant(order.tolist(), dup_at, n_jobs)


def test_interleaving_invariant_mirror_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    n_jobs = 4
    tags = [j for j in range(n_jobs) for _ in range(3)]

    @settings(max_examples=40, deadline=None)
    @given(order=st.permutations(tags),
           dup_at=st.lists(st.integers(0, len(tags) - 1), max_size=6))
    def check(order, dup_at):
        _check_invariant(order, dup_at, n_jobs)

    check()


def test_out_of_order_obit_never_double_frees():
    # JOBOBIT before its RUNJOB: the job ends without the mirror ever
    # charging its nodes — free_nodes must NOT exceed capacity
    q, r, o = _lifecycle(0)
    state = empty_state(8, 16, xp=np)
    for ev in (q, o, r):            # lifecycle order broken
        state, _ = apply_event(state, ev, idempotent=True)
    assert int(state.free_nodes) == 16
    assert int(state.jobs.state[0]) == DONE
    # the late RUNJOB backfilled the start time
    assert float(state.jobs.start_t[0]) == pytest.approx(10.0)


# ----------------------------------------------------------------------
# loss detection -> probe resync -> the co-simulation still completes
# ----------------------------------------------------------------------

class DropOnce:
    """Bus view that silently drops ONE specific seq from delivery."""

    def __init__(self, inner, drop_seq):
        self.inner, self.drop_seq = inner, drop_seq

    def read(self, consumer, max_events=None):
        return [e for e in self.inner.read(consumer, max_events)
                if e.seq != self.drop_seq]

    def __getattr__(self, name):
        return getattr(self.inner, name)


@pytest.mark.parametrize("drop_seq", [0, 4])
def test_lost_queuejob_heals_via_resync(drop_seq):
    trace = tiny_trace(10, seed=2)
    bus, em, _, twin = build_cosim(
        trace, view_wrap=lambda b: DropOnce(b, drop_seq),
        reorder_window=2)
    report = em.run(on_event=twin.pump, on_quiesce=twin.flush)
    assert report.n_jobs == len(trace)          # nothing stranded
    ing = twin.telemetry.ingest
    assert ing.gaps >= 1 and ing.lost >= 1 and ing.resyncs >= 1


# ----------------------------------------------------------------------
# deadline guard: deterministic ladder under an injected clock
# ----------------------------------------------------------------------

def _drive(spec, latencies):
    g = DeadlineGuard(spec)
    out = []
    for secs in latencies:
        lvl = g.plan()
        out.append(lvl)
        g.observe(lvl, secs)
    return g, out


def test_guard_ladder_walks_all_levels_on_sustained_misses():
    spec = GuardSpec(budget_s=1.0, safety=0.8, ewma_alpha=1.0,
                     recover_after=2)
    lat = (2.0, 2.0, 2.0, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1)
    g, trajectory = _drive(spec, lat)
    # climbs one level per miss; once every lower level's estimate is
    # poisoned (2s >> 0.8s headroom, alpha=1 so no decay) the predictive
    # planner PINS the ladder at hold-incumbent even though the comfy
    # counter keeps voting to step down — degraded-but-on-time beats
    # retrying a level known to blow the budget.
    assert trajectory == [0, 1, 2, 3, 3, 3, 3, 3, 3, 3]
    assert g.misses == 3
    assert g.engagements == sum(1 for lvl in trajectory if lvl > 0)
    # deterministic: same inputs, same trajectory
    _, t2 = _drive(spec, lat)
    assert t2 == trajectory
    # and the ladder state round-trips through the snapshot dict
    g3 = DeadlineGuard(g.spec).restore(g.to_dict())
    assert g3.plan() == g.plan()
    assert g3.misses == g.misses


def test_guard_recovers_after_transient_spike():
    # with a decaying EWMA a single spike escalates reactively, the
    # fast cycles at level 1 satisfy the hysteresis, and the planner
    # lets the ladder back down because level 0's estimate recovered
    spec = GuardSpec(budget_s=1.0, safety=0.8, ewma_alpha=0.1,
                     recover_after=2)
    g, trajectory = _drive(
        spec, (0.1, 0.1, 2.0, 0.1, 0.1, 0.1, 0.1))
    assert trajectory == [0, 0, 0, 1, 1, 0, 0]
    assert g.misses == 1


def test_guard_disabled_never_engages():
    g, trajectory = _drive(GuardSpec(budget_s=0.0), (9.0, 9.0, 9.0))
    assert trajectory == [0, 0, 0]
    assert g.misses == 0 and g.engagements == 0
    assert not g.spec.enabled


def test_twin_ladder_deterministic_under_fake_clock():
    def fake_clock_factory():
        c = itertools.count()
        return lambda: next(c) * 10.0          # every cycle "takes" 10s

    def run():
        trace = tiny_trace(8, seed=3)
        bus, em, _, twin = build_cosim(trace, guard=1.0,
                                       clock=fake_clock_factory())
        em.run(on_event=twin.pump, on_quiesce=twin.flush)
        return [(c.guard_level, c.deadline_miss)
                for c in twin.telemetry.cycles]

    a, b = run(), run()
    assert a == b                               # bit-deterministic
    levels = [lvl for lvl, _ in a]
    assert levels[0] == 0                       # starts at full fidelity
    assert max(levels) == 3                     # walked the whole ladder
    assert any(miss for _, miss in a)           # the 10s cycles missed
    res_names = [LEVEL_NAMES[lvl] for lvl in levels]
    assert "hold_incumbent" in res_names


def test_guarded_cycles_stamp_telemetry_and_stats():
    trace = tiny_trace(8, seed=4)
    bus, em, _, twin = build_cosim(trace, guard=60.0)
    em.run(on_event=twin.pump, on_quiesce=twin.flush)
    stats = twin.telemetry.resilience_stats()
    assert stats["cycles"] == len(twin.telemetry.cycles) > 0
    assert stats["guarded_cycles"] == stats["cycles"]
    assert stats["miss_rate"] == 0.0            # 60s budget never misses
    for c in twin.telemetry.cycles:
        assert c.deadline_s == 60.0 and c.margin_s > 0.0


# ----------------------------------------------------------------------
# crash-safe snapshots: bitwise decision parity on both backends
# ----------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_snapshot_restore_bitwise_decision_parity(tmp_path, backend):
    eng = DrainEngine(backend, interpret=(backend == "pallas"))
    trace = tiny_trace(10, seed=5)

    bus, em, _, twin = build_cosim(trace, engine=eng)
    em.run(on_event=twin.pump, on_quiesce=twin.flush)
    ref = decisions(twin)

    bus, em, _, twin = build_cosim(trace, engine=eng)
    mgr = CheckpointManager(str(tmp_path / backend))
    holder = {"twin": twin, "killed": False}

    def pump():
        t = holder["twin"]
        t.pump()
        if not holder["killed"] and len(t.telemetry.cycles) >= 4:
            t.snapshot(mgr)
            fresh = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                              max_jobs=em.max_jobs,
                              free_nodes_probe=lambda: em.free_nodes,
                              jobs_probe=em.jobs_view, engine=eng,
                              sleep=lambda s: None)
            step, app = fresh.restore(mgr)
            assert step == len(t.telemetry.cycles) and app is None
            assert len(fresh.telemetry.cycles) == step
            # the restored mirror is on the host, bit for bit
            for got, want in zip(jax.tree.leaves(fresh.state),
                                 jax.tree.leaves(t.state)):
                assert isinstance(got, np.ndarray)
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(got, want)
            holder["twin"] = fresh
            holder["killed"] = True

    report = em.run(on_event=pump,
                    on_quiesce=lambda: holder["twin"].flush())
    assert holder["killed"]
    assert report.n_jobs == len(trace)
    assert decisions(holder["twin"]) == ref     # bitwise


def test_snapshot_carries_app_extra(tmp_path):
    trace = tiny_trace(6, seed=6)
    bus, em, _, twin = build_cosim(trace)
    em.run(on_event=twin.pump, on_quiesce=twin.flush)
    mgr = CheckpointManager(str(tmp_path))
    twin.snapshot(mgr, app_extra={"emulator": em.snapshot_state(),
                                  "bus": bus.dump()})
    bus2 = EventBus()
    em2 = ClusterEmulator(trace, 16, bus=bus2)
    twin2 = SchedTwin(bus=bus2, qrun=em2.qrun, total_nodes=16,
                      max_jobs=em2.max_jobs, sleep=lambda s: None)
    step, app = twin2.restore(mgr)
    em2.restore_state(app["emulator"])
    assert em2.now == em.now and em2.free_nodes == em.free_nodes
    assert app["bus"] == bus.dump()
    assert decisions(twin2) == decisions(twin)
    assert twin2.telemetry.ingest.as_dict() == \
        twin.telemetry.ingest.as_dict()


# ----------------------------------------------------------------------
# chaos determinism: injections are pure functions of (seed, seq)
# ----------------------------------------------------------------------

def _chaos_delivery(spec, events, reads=8):
    bus = EventBus()
    view = ChaosBus(bus, spec)
    for ev in events:
        bus.publish(ev)
    out = []
    per_read = max(1, len(events) // reads)
    consumed = 0
    while consumed < len(events):
        try:
            got = view.read("c", per_read)
        except BusReadError:
            continue                # retry the same window
        consumed += per_read
        out.extend((e.seq, e.kind, e.time) for e in got)
    return out, dict(view.stats)


def test_chaos_bus_is_deterministic():
    spec = dataclasses.replace(DEFAULT_PROFILE, seed=13)
    events = [Event(EventKind.QUEUEJOB, float(j), j % 8,
                    {"nodes": 1.0, "est_runtime": 5.0})
              for j in range(64)]
    a, stats_a = _chaos_delivery(spec, events)
    b, stats_b = _chaos_delivery(spec, events)
    assert a == b and stats_a == stats_b
    assert sum(stats_a.values()) > 0            # profile actually fired
    # a different seed corrupts differently
    c, _ = _chaos_delivery(dataclasses.replace(spec, seed=14), events)
    assert c != a


def test_chaos_spec_validation():
    with pytest.raises(ValueError, match="drop_prob"):
        ChaosSpec(drop_prob=1.5)
    with pytest.raises(ValueError, match="reorder_delay"):
        ChaosSpec(reorder_delay=0)


def test_chaos_cosim_completes_and_counts(tmp_path):
    trace = tiny_trace(12, seed=7)
    bus, em, view, twin = build_cosim(
        trace, view_wrap=lambda b: ChaosBus(
            b, dataclasses.replace(DEFAULT_PROFILE, seed=3)),
        reorder_window=8)
    report = em.run(on_event=twin.pump, on_quiesce=twin.flush)
    assert report.n_jobs == len(trace)
    stats = twin.telemetry.resilience_stats()
    # whatever was injected must show up in the ingestion ledger
    if view.stats["duplicates"]:
        assert stats["duplicates"] > 0
    if view.stats["corruptions"]:
        assert stats["quarantined"] > 0
    if view.stats["read_failures"]:
        assert stats["read_retries"] > 0
