"""End-to-end system tests: the twin in the loop with the emulator."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.emulator import ClusterEmulator, FailureSpec
from repro.cluster.workload import JobSpec, paper_synthetic_trace
from repro.core.events import EventBus
from repro.core.policies import FCFS, PAPER_POOL, SJF, WFP
from repro.core.state import PackedState
from repro.core.twin import SchedTwin

from conftest import gathered_mirror


def tiny_trace(n=24, seed=0):
    rng = np.random.default_rng(seed)
    jobs = []
    t = 0.0
    for j in range(n):
        jobs.append(JobSpec(j, t, int(rng.integers(1, 12)),
                            float(rng.uniform(30, 300)),
                            float(rng.uniform(20, 280)), "t"))
        t += 4.0
    return jobs


def run_twin(trace, total_nodes=16, **twin_kw):
    bus = EventBus()
    em = ClusterEmulator(trace, total_nodes, bus=bus,
                         check_invariants=True)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=total_nodes,
                     max_jobs=em.max_jobs,
                     free_nodes_probe=lambda: em.free_nodes, **twin_kw)
    report = em.run(on_event=twin.pump)
    return report, twin


def run_static(trace, policy, total_nodes=16):
    em = ClusterEmulator(trace, total_nodes, check_invariants=True)
    return em.run(policy_id=policy)


def test_twin_completes_all_jobs():
    trace = tiny_trace()
    report, twin = run_twin(trace)
    assert report.n_jobs == len(trace)
    assert report.utilization > 0
    assert len(twin.telemetry.cycles) > 0


def test_twin_not_worse_than_worst_static():
    """The twin picks among the static policies, so its paper-score
    must not be worse than the WORST static policy's."""
    trace = paper_synthetic_trace(seed=1)
    rep_twin, _ = run_twin(trace, total_nodes=32)
    from repro.core.scoring import PAPER_WEIGHTS

    def score(rep):
        return (0.25 * rep.max_wait / 60 + 0.25 * rep.max_slowdown
                + 0.25 * rep.avg_wait / 60 + 0.25 * rep.avg_slowdown)

    worst = max(score(run_static(trace, p, 32)) for p in PAPER_POOL)
    assert score(rep_twin) <= worst * 1.05  # small slack: replanning noise


def test_policy_distribution_sums_to_100():
    trace = tiny_trace(30, seed=2)
    _, twin = run_twin(trace)
    dist = twin.telemetry.policy_start_distribution()
    assert abs(sum(dist.values()) - 100.0) < 1e-6
    assert set(dist) <= {"WFP", "FCFS", "SJF"}


def test_twin_recovery_replays_bus():
    trace = tiny_trace(16, seed=3)
    bus = EventBus()
    em = ClusterEmulator(trace, 16, bus=bus)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                     max_jobs=em.max_jobs)
    em.run(on_event=twin.pump)
    state_before = twin.state
    twin.recover()
    # replay rebuilds the same job table
    np.testing.assert_allclose(np.asarray(state_before.jobs.state),
                               np.asarray(twin.state.jobs.state))
    np.testing.assert_allclose(np.asarray(state_before.jobs.end_t),
                               np.asarray(twin.state.jobs.end_t), atol=1e-4)


def test_node_failure_requeues_and_finishes():
    trace = tiny_trace(20, seed=4)
    bus = EventBus()
    em = ClusterEmulator(trace, 16, bus=bus,
                         failures=[FailureSpec(time=30.0, nodes=8,
                                               duration=120.0)],
                         check_invariants=True)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                     max_jobs=em.max_jobs,
                     free_nodes_probe=lambda: em.free_nodes)
    report = em.run(on_event=twin.pump)
    assert report.n_jobs == 20          # everything still completed
    assert report.n_restarts >= 0       # victims were re-run


def test_stale_qrun_is_ignored():
    trace = tiny_trace(8, seed=5)
    bus = EventBus()
    em = ClusterEmulator(trace, 16, bus=bus)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                     max_jobs=em.max_jobs)
    em.run(on_event=twin.pump)
    # re-running an already-finished job must be a no-op
    free_before = em.free_nodes
    em.qrun([0], em.now)
    assert em.free_nodes == free_before


def test_extended_pool_also_drains():
    from repro.core.policies import EXTENDED_POOL
    trace = tiny_trace(20, seed=6)
    report, twin = run_twin(trace, pool=EXTENDED_POOL)
    assert report.n_jobs == 20


def test_ensemble_twin_drains():
    trace = tiny_trace(16, seed=7)
    report, twin = run_twin(trace, ensemble=4, ensemble_noise=0.3)
    assert report.n_jobs == 16


# ----------------------------------------------------------------------
# the job table as a window over the stream (``sync.JobSlots``)
# ----------------------------------------------------------------------

def _cosim(trace, total_nodes, max_jobs):
    bus = EventBus()
    em = ClusterEmulator(trace, total_nodes, bus=bus, check_invariants=True)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=total_nodes,
                     max_jobs=max_jobs,
                     free_nodes_probe=lambda: em.free_nodes,
                     jobs_probe=em.jobs_view)
    em.run(on_event=twin.pump, on_quiesce=twin.flush)
    assert not twin.dead_letters
    return twin


@pytest.mark.parametrize("seed", [0, 2])
def test_a_window_decides_as_the_whole_table(seed):
    """200 jobs on 16 nodes through a 32-slot job table: every slot is
    recycled several times, and every cycle's policy and qrun set are
    those of a twin whose table holds the whole stream; the costs sum
    over other slot positions, so they agree to float32 rounding."""
    from repro.cluster.workload import poisson_trace
    trace = poisson_trace(200, 16, 40.0, (1, 8), (30.0, 600.0), seed=seed)
    small, whole = _cosim(trace, 16, 32), _cosim(trace, 16, 256)
    a, b = small.telemetry.cycles, whole.telemetry.cycles
    assert len(a) == len(b) > 200
    assert max(c.live_jobs for c in a) <= 32
    assert sum(c.recycled for c in a) == 200
    for x, y in zip(a, b):
        assert (x.time, x.policy, x.started_jobs) == \
            (y.time, y.policy, y.started_jobs)
        assert x.costs.keys() == y.costs.keys()
        for name in x.costs:
            assert x.costs[name] == pytest.approx(y.costs[name], rel=1e-6)


def test_an_attached_twin_decides_as_the_reference():
    """A twin attached to a snapshot taken mid-stream, with a job table
    smaller than every id it sees, against the reference judge that
    starts from the same snapshot (``bench/attach.py``)."""
    from bench import attach, check, gen
    from bench import reference as ref
    from repro.cluster.workload import poisson_trace
    from repro.core.state import INVALID
    nodes, attach_at = 16, 120
    trace = poisson_trace(180, nodes, 25.0, (1, 8), (30.0, 600.0), seed=4)
    pre = ClusterEmulator(trace, nodes)
    pre.advance(lambda: int(np.count_nonzero(pre.state != INVALID))
                >= attach_at, policy_id=FCFS)
    snapshot = pre.snapshot_state()
    bus = EventBus()
    em = ClusterEmulator(trace, nodes, bus=bus, check_invariants=True)
    em.restore_state(snapshot)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=nodes, max_jobs=64,
                     free_nodes_probe=lambda: em.free_nodes,
                     jobs_probe=em.jobs_view)
    assert twin.attach()                     # a queue: it decides at once
    cycles = twin.telemetry.cycles
    assert em.advance(lambda: len(cycles) >= 60, on_event=twin.pump)
    assert not twin.dead_letters
    first = cycles[0]
    assert first.stages["twin.attach"] > 0.0 and first.live_jobs > 0
    assert all(c.stages["twin.attach"] == 0.0 for c in cycles[1:])
    assert sum(c.recycled for c in cycles) > 0
    names = list(twin.pool.names)
    plain = gen.as_trace([j.submit_t for j in trace],
                         [j.nodes for j in trace],
                         [j.est_runtime for j in trace],
                         [j.true_runtime for j in trace])
    out = attach.attach_episode(
        plain, nodes, ref.parse_pool("paper"), snapshot,
        [check.Cycle(c.time, names.index(c.policy),
                     [c.costs[n] for n in names], c.started_jobs)
         for c in cycles], judge=range(len(cycles)))
    assert out["judged"] == len(cycles)
    assert (out["off"], out["missing"]) == (0, 0), out
    assert out["cost_gap"] < 1e-3


# ----------------------------------------------------------------------
# a decision's results reach the host in one read (``Decision.words``)
# ----------------------------------------------------------------------

class FieldByFieldTwin(SchedTwin):
    """A twin that also reads each decision's fields on their own, one
    read each, as the record of the cycle would have them."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.by_field = []

    def _record_cycle(self, t, level, seconds, decision, race_out, names,
                      source, words, ids):
        field = lambda x: {n: float(v) for n, v in          # noqa: E731
                           zip(names, np.asarray(x))}
        terms = {t: np.asarray(v)
                 for t, v in (decision.cost_terms or {}).items()}
        self.by_field.append(dict(
            policy=names[int(np.asarray(decision.policy_index))],
            costs=field(decision.costs),
            term_costs={n: {t: float(v[i]) for t, v in terms.items()}
                        for i, n in enumerate(names)},
            cost_ci=(field(decision.cost_ci)
                     if decision.cost_ci is not None else {}),
            fan_width=(field(decision.fan_width)
                       if decision.fan_width is not None else {}),
            started_jobs=ids[np.nonzero(
                np.asarray(decision.run_mask))[0]].tolist()))
        return super()._record_cycle(t, level, seconds, decision, race_out,
                                     names, source, words, ids)


def _same_bits(a: dict, b: dict) -> bool:
    return (list(a) == list(b)
            and all(np.float32(a[k]).view(np.int32)
                    == np.float32(b[k]).view(np.int32) for k in a))


def _fan_modes():
    from repro.core.fan import FanSpec
    from repro.core.race import RaceSpec
    fan = FanSpec(n=4, runtime_noise=0.3, seed=1)
    return {"fan": {"fan": fan}, "ensemble": {"ensemble": 3},
            "race": {"race": RaceSpec(fan=dataclasses.replace(fan, n=8),
                                      f0=2)}}


@pytest.mark.parametrize("trace,mode", [
    ("paper0", "plain"), ("paper1", "plain"), ("paper2", "plain"),
    ("tiny", "fan"), ("tiny", "ensemble"), ("tiny", "race")])
def test_every_cycle_record_is_its_decision_read_field_by_field(trace,
                                                                mode):
    """Three paper traces, 32 nodes, a 256-slot table (and a tiny trace
    under each fan mode): each cycle's record, built from the one read
    of its decision's words, is what reading every field of the same
    decision on its own gives."""
    if trace == "tiny":
        jobs, nodes, least = tiny_trace(16, seed=7), 16, 16
        kw = _fan_modes()[mode]
    else:
        jobs = paper_synthetic_trace(seed=int(trace[-1]))
        nodes, least = 32, 290
        kw = {}
    bus = EventBus()
    em = ClusterEmulator(jobs, nodes, bus=bus, check_invariants=True)
    twin = FieldByFieldTwin(bus=bus, qrun=em.qrun, total_nodes=nodes,
                            max_jobs=256,
                            free_nodes_probe=lambda: em.free_nodes, **kw)
    em.run(on_event=twin.pump)
    cycles = twin.telemetry.cycles
    assert len(cycles) == len(twin.by_field) >= least
    for c, want in zip(cycles, twin.by_field):
        assert c.policy == want["policy"]
        assert c.started_jobs == want["started_jobs"]
        assert _same_bits(c.costs, want["costs"])
        assert c.term_costs.keys() == want["term_costs"].keys()
        for name in c.term_costs:
            assert len(c.term_costs[name]) == 4
            assert _same_bits(c.term_costs[name], want["term_costs"][name])
        assert _same_bits(c.cost_ci, want["cost_ci"])
        assert _same_bits(c.fan_width, want["fan_width"])
        assert bool(c.cost_ci) == (mode != "plain")


# ----------------------------------------------------------------------
# the mirror reaches the device in one buffer (``PackedState``)
# ----------------------------------------------------------------------

class StateParityTwin(SchedTwin):
    """A twin that also decides each cycle from its mirror as a
    ``SimState`` gathered in the test, beside its own decision from the
    one buffer it uploaded."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.from_state = []

    def _decide_at_level(self, level, state):
        assert isinstance(state, PackedState) and state.words.ndim == 1
        mirror, ids = gathered_mirror(self.state, self.slots)
        d = self.engine.decide(jax.device_put(mirror), self.pool.spec,
                               self.objective)
        self.from_state.append((d.unpack(np.asarray(d.words)), ids))
        return super()._decide_at_level(level, state)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_packed_upload_decides_as_the_mirror_state(seed):
    """Paper traces, 32 nodes, a 256-slot table: every cycle's record,
    decided from the one uploaded buffer, is the decision of the same
    mirror passed as a ``SimState``, bit for bit."""
    bus = EventBus()
    em = ClusterEmulator(paper_synthetic_trace(seed=seed), 32, bus=bus,
                         check_invariants=True)
    twin = StateParityTwin(bus=bus, qrun=em.qrun, total_nodes=32,
                           max_jobs=256,
                           free_nodes_probe=lambda: em.free_nodes)
    em.run(on_event=twin.pump)
    cycles = twin.telemetry.cycles
    assert len(cycles) == len(twin.from_state) >= 290
    names = list(twin.pool.names)
    for c, (view, ids) in zip(cycles, twin.from_state):
        assert c.upload_buffers == 1
        assert c.policy == names[view.policy_index]
        assert c.started_jobs == ids[np.nonzero(view.run_mask)[0]].tolist()
        assert _same_bits(c.costs, dict(zip(names, view.costs)))
        assert c.term_costs.keys() == set(names)
        for i, name in enumerate(names):
            assert _same_bits(c.term_costs[name], {
                t: v[i] for t, v in view.cost_terms.items()})
