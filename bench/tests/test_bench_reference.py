"""The plain reference agrees with the program: replay grids and twin
decisions on paper-trace seeds, with the paper pool (k=3) and the sweep
pool (k=32), and with the Pallas pass (interpreted here).  This keeps
the reference honest as the program changes."""
import copy
import json

import numpy as np
import pytest

from bench import check, gen, reference as ref, spec
from repro.cluster.emulator import ClusterEmulator
from repro.cluster.workload import (paper_synthetic_trace, poisson_trace,
                                    stack_scenarios)
from repro.core.engine import DrainEngine
from repro.core.events import EventBus
from repro.core.policies import parse_pool
from repro.core.twin import SchedTwin

SWEEP = "extended,wfp:a=1..5x5:tau=600..7200x5"
PAPER = json.loads((spec.ROOT / "bench/configs/paper32.json").read_text())
#: A Poisson stream of heavy-tailed walltimes, reordered within runs of
#: 50 jobs: the other trace family's settings, on a cluster of 64 nodes.
POISSON = {"total_nodes": 64, "n_jobs": 120, "max_jobs": 128,
           "trace": {"family": "poisson", "draws_seed": 0,
                     "shuffle_group": 50, "mean_gap": 60.0,
                     "node_range": [1, 64], "walltime_range": [60.0, 86400.0],
                     "accuracy": [0.3, 1.0]}}
CONFIGS = {"paper32": PAPER, "poisson": POISSON}


def _make(config, seed):
    return gen.make_trace(spec.family(config["trace"]["family"]), config,
                          seed)


def _same_jobs(ours, theirs):
    for field in ("submit_t", "nodes", "est_runtime", "true_runtime"):
        assert np.array_equal(getattr(ours, field),
                              [getattr(j, field) for j in theirs]), field


def test_generator_copy_draws_the_programs_jobs():
    _same_jobs(gen.draw(spec.family("paper"), PAPER, 3),
               paper_synthetic_trace(seed=3))
    t = POISSON["trace"]
    _same_jobs(gen.draw(spec.family("poisson"), POISSON, 4), poisson_trace(
        n_jobs=POISSON["n_jobs"], total_nodes=POISSON["total_nodes"],
        mean_gap=t["mean_gap"], node_range=tuple(t["node_range"]),
        walltime_range=tuple(t["walltime_range"]),
        accuracy=tuple(t["accuracy"]), seed=4, heavy_tail=True))


@pytest.mark.parametrize("config", ["paper32", "poisson"])
def test_seeds_reorder_one_draw_of_jobs(config):
    """Every seed gets the jobs of the configuration's fixed draw, each
    group's jobs in an order of the seed's, and the same span."""
    cfg = CONFIGS[config]
    family = spec.family(cfg["trace"]["family"])
    base = gen.draw(family, cfg, cfg["trace"]["draws_seed"])
    a, b = _make(cfg, 2**31 + 5), _make(cfg, 7)
    assert not np.array_equal(a.nodes, b.nodes)
    lo = 0
    for n in family.groups(cfg):
        for t in (a, b):
            jobs = lambda x: sorted(zip(x.nodes[lo:lo + n],     # noqa: E731
                                        x.est_runtime[lo:lo + n],
                                        x.true_runtime[lo:lo + n]))
            assert jobs(t) == jobs(base)
        lo += n
    for t in (a, b):
        assert t.submit_t[0] == base.submit_t[0]
        assert t.submit_t[-1] == pytest.approx(base.submit_t[-1], rel=1e-12)
        assert np.all(np.diff(t.submit_t) >= 0)
    assert np.array_equal(_make(cfg, 7).nodes, b.nodes)


def test_a_cells_traces_are_one_set_in_the_seeds_order():
    """Every run of a cell gets the same traces, in an order of its
    seed's: seeds change the order of the work, not the work."""
    family = spec.family("paper")
    a = gen.scenario_traces(family, PAPER, 16, 2**31 + 5)
    b = gen.scenario_traces(family, PAPER, 16, 7)
    key = lambda t: t.nodes.tobytes() + t.est_runtime.tobytes()  # noqa: E731
    assert sorted(map(key, a)) == sorted(map(key, b))
    assert len(set(map(key, a))) == 16
    assert list(map(key, a)) != list(map(key, b))
    again = gen.scenario_traces(family, PAPER, 16, 7)
    assert list(map(key, again)) == list(map(key, b))


def _twin_cycles(trace, nodes, pool_text, backend="reference"):
    engine = DrainEngine(backend)
    pool = parse_pool(pool_text)
    bus = EventBus()
    em = ClusterEmulator(gen.jobspecs(trace), nodes, bus=bus,
                         engine=engine)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=nodes,
                     max_jobs=em.max_jobs, pool=pool,
                     free_nodes_probe=lambda: em.free_nodes, engine=engine)
    em.run(on_event=twin.pump)
    names = list(pool.names)
    return [check.Cycle(c.time, names.index(c.policy),
                        [c.costs[n] for n in names], c.started_jobs)
            for c in twin.telemetry.cycles]


@pytest.mark.parametrize("seed", [0, 1])
def test_twin_decisions_paper_pool(seed):
    trace = _make(PAPER, seed)
    cycles = _twin_cycles(trace, 32, "paper")
    out = check.twin_episode(trace, 32, ref.parse_pool("paper"), cycles)
    assert out["judged"] == 2 * len(trace) == len(cycles)
    assert out["off"] == 0 and out["missing"] == 0
    assert out["cost_gap"] < 1e-5


def test_twin_decisions_sweep_pool():
    config = copy.deepcopy(PAPER)
    config["trace"]["phases"] = config["trace"]["phases"][:2]
    trace = _make(config, 2)
    cycles = _twin_cycles(trace, 32, SWEEP)
    out = check.twin_episode(trace, 32, ref.parse_pool(SWEEP), cycles)
    assert out["judged"] == 2 * len(trace)
    assert out["off"] == 0 and out["missing"] == 0
    assert out["cost_gap"] < 1e-4


def _grid(traces, nodes, pool_text, backend, max_jobs):
    """The program's grid judged by the check, after its schedules are
    held to the reference's own replays exactly."""
    scen = stack_scenarios([gen.jobspecs(t) for t in traces], nodes,
                           max_jobs=max_jobs)
    out = DrainEngine(backend).replay_grid(scen, parse_pool(pool_text).spec)
    metrics = np.stack([np.asarray(f) for f in out.metrics], axis=-1)
    pool = ref.parse_pool(pool_text)
    parts = []
    for s, t in enumerate(traces):
        start = np.asarray(out.start_t)[s, :, :len(t)]
        end = np.asarray(out.end_t)[s, :, :len(t)]
        for p, pol in enumerate(pool):
            r = ref.replay(t, nodes, pol)
            assert np.array_equal(start[p], r.start.astype(np.float32))
            assert np.array_equal(end[p], r.end.astype(np.float32))
        parts.append(check.grid_scenario(
            t, nodes, pool, start, end, metrics[s], np.asarray(out.costs)[s],
            int(out.best[s]), np.asarray(out.deadlocked)[s]))
    totals = check.merge(parts)
    assert totals["ties"] == 0 and totals["passes"] > 0
    return check.grid_numbers(totals)


def test_replay_grid_sweep_pool_on_paper_traces():
    traces = [_make(PAPER, s) for s in (4, 5, 6)]
    out = _grid(traces, 32, SWEEP, "reference", 256)
    assert out["forks_off_pct"] == 0.0
    assert out["metric_gap"] < 1e-5 and out["cost_gap"] < 1e-5


def test_replay_grid_pallas_pass_agrees():
    config = {"total_nodes": 16, "n_jobs": 40,
              "trace": {"family": "poisson", "mean_gap": 15.0,
                        "node_range": [1, 16],
                        "walltime_range": [30.0, 900.0],
                        "accuracy": [0.3, 1.0]}}
    traces = [_make(config, s) for s in (7, 8)]
    out = _grid(traces, 16, "extended", "pallas", 64)
    assert out["forks_off_pct"] == 0.0
    assert out["metric_gap"] < 1e-5 and out["cost_gap"] < 1e-5


def _two_waiting(est1, est2):
    """One node; job 0 runs first, then jobs 1 and 2 (true runtime 5 s)
    wait for it with estimates ``est1`` and ``est2``."""
    return gen.Trace(np.array([0.0, 1.0, 2.0]), np.array([1, 1, 1]),
                     np.array([10.0, est1, est2]),
                     np.array([10.0, 5.0, 5.0]))


@pytest.mark.parametrize("est1,verdict", [(1000.001, "ties"),
                                          (1100.0, "off")])
def test_grid_fork_takes_near_ties_either_way(est1, verdict):
    """Under SJF job 2 (estimate 1000) goes before job 1.  A schedule
    that runs job 1 first is a near tie when the estimates lie within
    float32 rounding of a key, and one decision off when they do not;
    either way the later passes are judged in that schedule's context
    and agree."""
    sjf = ref.parse_pool("sjf")[0]
    trace = _two_waiting(est1, 1000.0)
    own = ref.replay(trace, 1, sjf)
    assert check.grid_fork(trace, 1, sjf, own.start, own.end, False) == {
        "passes": 5, "off": 0, "ties": 0}
    flipped = ref.replay(_two_waiting(1000.0, est1), 1, sjf)
    assert flipped.start.tolist() == [0.0, 10.0, 15.0]
    out = check.grid_fork(trace, 1, sjf, flipped.start, flipped.end, False)
    assert out == {"passes": 5, "off": 0, "ties": 0, verdict: 1}


def test_grid_fork_counts_a_wrong_end_and_a_missing_start():
    fcfs = ref.parse_pool("fcfs")[0]
    trace = _two_waiting(1000.0, 1000.0)
    own = ref.replay(trace, 1, fcfs)
    end = own.end.copy()
    end[1] += 1.0
    assert check.grid_fork(trace, 1, fcfs, own.start, end, False)["off"] >= 1
    start = own.start.copy()
    start[2] += 1.0
    assert check.grid_fork(trace, 1, fcfs, start, own.end, False)["off"] >= 1


def test_easy_pass_counts_every_job_ending_at_the_shadow_instant():
    """Two running jobs end together at t=100; the head needs both
    jobs' nodes, so the reservation is t=100 and the surplus there is
    what both free less the head's need."""
    f32 = np.float32
    nodes = np.array([4, 4, 6, 2], np.int64)      # slots 2, 3 queued
    est = np.array([0, 0, 50, 200], f32)
    started = ref.easy_pass(f32(0), 0, np.array([2, 3]), nodes, est,
                            np.array([100, 100], f32), np.array([4, 4]))
    # head = slot 2 (6 nodes) at t=100 with 8 freed: extra = 2, so slot
    # 3 (2 nodes, runs past t=100) may not start now: nothing is free
    assert started == []
    started = ref.easy_pass(f32(0), 2, np.array([2, 3]), nodes, est,
                            np.array([100, 100], f32), np.array([4, 4]))
    assert started == [3]


@pytest.mark.parametrize("est_c,verdict", [(1000.001, "ties"),
                                           (1100.0, "off")])
def test_grid_fork_takes_a_near_tie_that_changes_the_head(est_c, verdict):
    """Ten nodes, all held by job 0 until t=100; then A (2 nodes,
    estimate 10), B (2 nodes, 1000) and C (9 nodes, ``est_c``) wait.
    Under SJF A and B start at t=100 and C waits.  A schedule that ranks
    C before B makes C the head, which keeps B out of the backfill: a
    near tie when C's estimate lies within rounding of B's."""
    sjf = ref.parse_pool("sjf")[0]

    def trace(c):
        return gen.Trace(np.array([0.0, 1.0, 2.0, 3.0]),
                         np.array([10, 2, 2, 9]),
                         np.array([100.0, 10.0, 1000.0, c]),
                         np.array([100.0, 10.0, 5.0, 5.0]))
    flipped = ref.replay(trace(999.0), 10, sjf)
    assert flipped.start.tolist() == [0.0, 100.0, 115.0, 110.0]
    out = check.grid_fork(trace(est_c), 10, sjf, flipped.start, flipped.end,
                          False)
    assert out[verdict] >= 1 and out["passes"] == 7
    assert out["off" if verdict == "ties" else "ties"] == 0
