"""The twin's own measurement: stage spans and counters on every cycle
(``core/telemetry.StageMeter``), the decision-span statistics, and the
device stages' named scopes in the engine's lowered programs."""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.workload import poisson_trace, stack_scenarios
from repro.core import telemetry
from repro.core.engine import DrainEngine
from repro.core import engine as engine_mod
from repro.core.events import Event, EventBus, EventKind
from repro.core.objective import resolve_goal
from repro.core.policies import parse_pool
from repro.core.telemetry import STAGES, CycleRecord, Telemetry
from repro.core.twin import SchedTwin

from conftest import make_cluster_state

NODES, MAX_JOBS = 16, 32


def _queue(j, t, nodes=4, est=100.0):
    return Event(EventKind.QUEUEJOB, t, j,
                 {"nodes": float(nodes), "est_runtime": est})


class ScriptedTwin:
    """A twin on a hand-fed bus whose ``qrun`` publishes each start as
    a RUNJOB, and a host-side free-node count for the probe."""

    def __init__(self, qrun_extra=None):
        self.bus = EventBus()
        self.free = NODES
        self.nodes = {}
        self.qrun_extra = qrun_extra
        self.twin = SchedTwin(bus=self.bus, qrun=self.qrun,
                              total_nodes=NODES, max_jobs=MAX_JOBS,
                              free_nodes_probe=lambda: self.free)

    def publish(self, ev):
        if ev.kind == EventKind.QUEUEJOB:
            self.nodes[ev.job_id] = int(ev.payload["nodes"])
        if ev.kind == EventKind.JOBOBIT:
            self.free += self.nodes[ev.job_id]
        self.bus.publish(ev)

    def qrun(self, job_ids, t):
        for j in job_ids:
            self.free -= self.nodes[j]
            self.bus.publish(Event(EventKind.RUNJOB, t, j))
        if self.qrun_extra is not None:
            self.qrun_extra()

    def pump(self):
        """(the pump's own span in seconds, the cycle it recorded)."""
        cycles = self.twin.telemetry.cycles
        n0 = len(cycles)
        t0 = time.perf_counter()
        self.twin.pump()
        dt = time.perf_counter() - t0
        return dt, (cycles[-1] if len(cycles) > n0 else None)


#: A decision's results reach the host in one read (``Decision.words``),
#: whatever its goal's terms.
DECISION_READS = 1


#: A twin's first cycle reads its pool's families and θ once, for the
#: engine's hoist plan.
HOIST_PLAN_READS = 2


def test_every_cycle_has_all_stages_and_exact_counts():
    s = ScriptedTwin()
    # pump: (events, reads before the decision) by hand: the mirror is
    #   on the host, so ingest reads nothing from the device; only the
    #   twin's first cycle reads, for its hoist plan.
    s.publish(_queue(0, 0.0, nodes=10, est=300.0))
    a = s.pump()                                  # QUEUEJOB 0: starts
    s.publish(_queue(1, 5.0, nodes=10, est=200.0))
    b = s.pump()                  # RUNJOB 0 + QUEUEJOB 1: 6 free, waits
    s.publish(Event(EventKind.JOBOBIT, 50.0, 0))
    c = s.pump()                                  # JOBOBIT 0: starts 1
    s.publish(_queue(2, 60.0, nodes=2, est=50.0))
    d = s.pump()                       # RUNJOB 1 + QUEUEJOB 2: starts 2
    s.publish(Event(EventKind.RUNJOB, 61.0, 3))   # unknown job: no cycle
    e = s.pump()
    # (events, reads before the decision, jobs started)
    expected = {"a": (1, HOIST_PLAN_READS, 1), "b": (2, 0, 0),
                "c": (1, 0, 1), "d": (2, 0, 1)}
    for name, (dt, rec) in zip("abcd", (a, b, c, d)):
        events, reads, started = expected[name]
        assert rec is not None, name
        assert set(rec.stages) == set(STAGES), name
        assert all(v >= 0.0 for v in rec.stages.values()), name
        assert sum(rec.stages.values()) <= dt, name
        assert rec.events == events, name
        assert rec.host_reads == reads + DECISION_READS, name
        assert rec.uploads == 1, name
        assert rec.n_started == started, name
        assert (rec.stages["twin.qrun"] > 0.0) == bool(started), name
        # every pump runs each stage but qrun (only when it starts jobs)
        # and attach (only in ``SchedTwin.attach``)
        for stage in set(STAGES) - {"twin.attach", "twin.qrun"}:
            assert rec.stages[stage] > 0.0, (name, stage)
        assert rec.stages["twin.attach"] == 0.0, name
    assert e[1] is None
    assert len(s.twin.telemetry.cycles) == 4
    steady = Telemetry()
    steady.cycles = s.twin.telemetry.cycles[1:]
    assert steady.cycle_latency_stats()["host_reads"] == 1.0
    # the decision span is decide + fetch, as before
    for rec in s.twin.telemetry.cycles:
        assert (rec.stages["twin.decide"] + rec.stages["twin.fetch"]
                <= rec.wall_seconds)


@pytest.mark.parametrize("n_events", [1, 8])
def test_one_upload_per_decision_and_no_read_before_it(n_events):
    s = ScriptedTwin()
    for j in range(n_events):
        s.publish(_queue(j, float(j), nodes=4))
    before = telemetry._ProcessCounts.uploads
    _, first = s.pump()
    assert first.events == n_events and first.n_started >= 1
    assert telemetry._ProcessCounts.uploads - before == 1
    assert first.host_reads == HOIST_PLAN_READS + DECISION_READS
    # the next pump carries the RUNJOBs the first one's qrun published
    s.publish(_queue(n_events, 100.0, nodes=1))
    _, second = s.pump()
    assert second.events == first.n_started + 1
    assert second.host_reads == DECISION_READS
    for rec in (first, second):
        assert rec.uploads == 1
        assert rec.stages["twin.upload"] > 0.0
    assert s.twin.telemetry.cycle_latency_stats()["uploads"] == 1.0


def test_a_nested_entry_point_meters_its_own_cycle():
    # push mode: the first QUEUEJOB's cycle starts job 0, whose qrun
    # fires on_event for RUNJOB 0 (no cycle) and for QUEUEJOB 1, which
    # runs a cycle of its own inside the first one's qrun
    s = ScriptedTwin()
    s.twin.bus.subscribe(s.twin.on_event)

    def queue_one_more():
        if 1 not in s.nodes:
            s.publish(_queue(1, 0.0, nodes=2))

    s.qrun_extra = queue_one_more
    before = telemetry._ProcessCounts.host_reads
    s.publish(_queue(0, 0.0, nodes=4))
    reads = telemetry._ProcessCounts.host_reads - before
    outer, inner = s.twin.telemetry.cycles
    assert outer.started_jobs == [0] and inner.started_jobs == [1]
    for rec in (outer, inner):
        assert set(rec.stages) == set(STAGES)
        assert rec.host_reads >= DECISION_READS
    # each read is counted once; the cycle-less RUNJOBs fold into the
    # entry that ran them, and the nested cycle's time lies in qrun
    assert outer.host_reads + inner.host_reads == reads
    # QUEUEJOB 0 + RUNJOB 0, and QUEUEJOB 1 + RUNJOB 1
    assert outer.events == 2 and inner.events == 2
    assert sum(inner.stages.values()) <= outer.stages["twin.qrun"]


def test_a_raced_cycle_counts_the_reads_of_its_rungs():
    from repro.core.fan import FanSpec
    from repro.core.race import RaceSpec
    counts = {}
    for mode, kw in (("plain", {}),
                     ("raced", {"race": RaceSpec(fan=FanSpec(n=8), f0=2)})):
        s = ScriptedTwin()
        s.twin = SchedTwin(bus=s.bus, qrun=s.qrun, total_nodes=NODES,
                           max_jobs=MAX_JOBS,
                           free_nodes_probe=lambda: s.free, **kw)
        s.publish(_queue(0, 0.0, nodes=10))
        _, rec = s.pump()
        counts[mode] = rec
    plain, raced = counts["plain"], counts["raced"]
    assert raced.race_rungs >= 2
    # each rung reads at least its members' costs, deadlocks, and the
    # rung's cost, CI and width
    assert raced.host_reads >= plain.host_reads + 5 * raced.race_rungs


def test_a_cycle_counts_the_compiles_of_its_pump():
    def compile_fresh():
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3)).block_until_ready()

    s = ScriptedTwin(qrun_extra=compile_fresh)
    s.publish(_queue(0, 0.0))
    _, rec = s.pump()
    assert rec.compiles >= 1
    s.publish(_queue(1, 1.0, nodes=16))           # cannot start: no qrun
    _, rec = s.pump()
    assert rec.n_started == 0 and rec.stages["twin.qrun"] == 0.0


def test_one_compile_listener_per_process():
    for _ in range(3):
        ScriptedTwin()
    x = jnp.ones(5)
    before = telemetry._ProcessCounts.compiles
    jax.jit(lambda x: x - 7.0)(x).block_until_ready()
    assert telemetry._ProcessCounts.compiles - before == 1


def test_fetch_counts_device_arrays_only():
    x = jnp.ones(2)
    before = telemetry._ProcessCounts.host_reads
    assert telemetry.fetch(np.ones(2)).shape == (2,)
    assert telemetry.fetch(x).shape == (2,)
    assert telemetry._ProcessCounts.host_reads - before == 1


def test_stages_nest_in_the_drivers_pump_span_of_a_profile(tmp_path):
    from bench import trace
    from bench.record import span
    from bench.spec import driver
    Driver = driver("twin")
    s = ScriptedTwin()
    s.publish(_queue(0, 0.0))
    s.pump()                                      # compile outside the trace
    s.publish(_queue(1, 1.0))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with span("pump", True):
            s.twin.pump()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    _, host = trace.read(path, Driver.spans + STAGES)
    pumps = [e for e in host if e.name == "pump"]
    assert len(pumps) == 1
    p = pumps[0]
    found = {e.name: e for e in host if e.name in STAGES}
    assert set(found) == set(STAGES) - {"twin.attach"}
    for e in found.values():
        assert p.start <= e.start and e.start + e.dur <= p.start + p.dur
    # given the stage names, an idle gap inside a stage is named by it
    mid = found["twin.ingest"].start + found["twin.ingest"].dur // 2
    assert trace._label(host, mid) == "twin.ingest"


def test_cycle_latency_stats_by_nearest_rank():
    tm = Telemetry()
    for i in range(1, 101):
        stages = dict.fromkeys(STAGES, 0.0)
        stages["twin.ingest"] = i * 1e-4
        tm.record(CycleRecord(time=float(i), wall_seconds=i * 1e-3,
                              policy="FCFS", costs={}, n_started=0,
                              started_jobs=[], stages=stages,
                              host_reads=i % 4, events=i % 3))
    st = tm.cycle_latency_stats()
    assert st["n"] == 100
    assert st["p50_s"] == pytest.approx(0.050)       # ws[n//2] read 0.051
    assert st["p95_s"] == pytest.approx(0.095)
    assert st["mean_s"] == pytest.approx(0.0505)
    assert st["max_s"] == pytest.approx(0.100)
    assert st["host_reads"] == pytest.approx(1.5)
    assert st["events"] == pytest.approx(1.0)
    assert st["stage_p50_s"]["twin.ingest"] == pytest.approx(0.0050)
    assert st["stage_p50_s"]["twin.qrun"] == 0.0
    empty = Telemetry().cycle_latency_stats()
    assert empty["n"] == 0 and set(empty["stage_p50_s"]) == set(STAGES)


SCOPES = ("keys", "pass", "advance", "select")


def _scopes(lowered) -> set:
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return {part for n in names for part in n.split("/")} & set(SCOPES)


def test_decide_names_its_device_stages():
    eng = DrainEngine("reference")
    pool = parse_pool("paper").spec
    state = make_cluster_state()
    lowered = engine_mod._decide.lower(eng, state, pool, resolve_goal(None),
                                       eng.plan(pool))
    assert _scopes(lowered) == set(SCOPES)


def test_batched_replay_names_its_device_stages():
    eng = DrainEngine("reference")
    traces = [poisson_trace(12, 16, 20.0, (1, 8), (30.0, 300.0), seed=s)
              for s in range(2)]
    scen = stack_scenarios(traces, 16, max_jobs=16)
    lowered = eng.lower_replay_grid(scen, parse_pool("extended").spec)
    assert _scopes(lowered) == set(SCOPES)


def test_twin_loop_prints_every_stage(monkeypatch, capsys):
    from repro.launch import twin_loop
    monkeypatch.setattr("sys.argv", [
        "twin_loop", "--trace", "poisson", "--jobs", "6", "--nodes", "4",
        "--backend", "reference", "--no-compile-cache"])
    twin_loop.main()
    line = next(x for x in capsys.readouterr().out.splitlines()
                if x.startswith("cycle stages, p50 ms: "))
    for name in STAGES:
        assert f" {name.split('.')[-1]} " in line, name
    assert "host reads" in line and "events" in line
    assert "uploads 1.0" in line


def test_twin_loop_attaches_to_a_lublin_stream(monkeypatch, capsys):
    """The Polaris-scale path at a CPU size: a Lublin stream run by the
    emulator's own scheduler through 40 submissions, then a twin with
    32 job slots attached; its stage line carries the job table."""
    from repro.launch import twin_loop
    monkeypatch.setattr("sys.argv", [
        "twin_loop", "--trace", "lublin", "--jobs", "60", "--nodes", "64",
        "--attach", "40", "--window", "32", "--backend", "reference",
        "--no-compile-cache"])
    twin_loop.main()
    out = capsys.readouterr().out.splitlines()
    assert any(x.startswith("attached at ") for x in out)
    line = next(x for x in out if x.startswith("cycle stages, p50 ms: "))
    for part in (" attach ", " slots ", "live_jobs ", "recycled ",
                 "window_full 0"):
        assert part in line, part
