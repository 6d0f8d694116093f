"""First-class Objective API (core/objective.py, DESIGN.md §8).

The contract under test:

* the goal grammar parses, canonicalizes and round-trips
  (``parse_objective(obj.spec) == obj``), and rejects malformed goals;
* ``objective="score"`` is BIT-IDENTICAL to the legacy ``ScoreWeights``
  path on BOTH pass backends (the redesign must not move a single
  decision), and ``weights=`` still works everywhere — lifted with a
  ``DeprecationWarning``, bit-identically;
* constrained goals implement the feasibility-fallback semantics:
  feasible candidates always beat infeasible ones; among all-infeasible
  pools the least total violation wins;
* lexicographic goals break exact primary ties by the next level;
* ``replay_grid``/``replay`` select per scenario under the goal
  (``ReplayOutcome.costs``/``best``), deadlocked forks excluded.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import whatif
from repro.core.des import DrainMetrics
from repro.core.engine import DrainEngine
from repro.core.objective import (DEFAULT_OBJECTIVE, Constrained,
                                  Constraint, Lexicographic, PaperScore,
                                  Weighted, metrics_from_rows,
                                  normalize_objective, parse_objective,
                                  register_objective, report_costs,
                                  resolve_goal)
from repro.core.policies import EXTENDED_POOL, PAPER_POOL, parse_pool
from repro.core.scoring import PAPER_WEIGHTS, ScoreWeights, policy_cost

from conftest import make_cluster_state

REF = DrainEngine("reference")
PAL = DrainEngine("pallas", interpret=True)


def _metrics(**cols):
    """DrainMetrics with a (k,) candidate axis; unspecified fields 0."""
    k = len(next(iter(cols.values())))
    full = {f: jnp.asarray(cols.get(f, [0.0] * k), dtype=jnp.float32)
            for f in DrainMetrics._fields}
    return DrainMetrics(**full)


# ----------------------------------------------------------------------
# Grammar: parse / normalize / round-trip.
# ----------------------------------------------------------------------

def test_parse_single_metric_and_aliases():
    assert parse_objective("avg_wait") == Weighted(((1.0, "avg_wait"),))
    assert parse_objective("util") == Weighted(((1.0, "utilization"),))
    assert parse_objective("UTIL").spec == "utilization"


def test_parse_weighted_combination():
    obj = parse_objective("0.5*avg_wait+0.5*max_slowdown")
    assert obj == Weighted(((0.5, "avg_wait"), (0.5, "max_slowdown")))
    m = _metrics(avg_wait=[10.0, 20.0], max_slowdown=[4.0, 2.0])
    np.testing.assert_allclose(np.asarray(obj.costs(m)), [7.0, 11.0])


def test_parse_score_and_custom_weights():
    assert parse_objective("score") == PaperScore()
    obj = parse_objective("score:max_wait=0.5:avg_wait=0.5"
                          ":max_slowdown=0:avg_slowdown=0")
    assert obj.weights == ScoreWeights(0.5, 0.0, 0.5, 0.0)


def test_parse_constrained_and_lex():
    obj = parse_objective("min:avg_wait@util>=0.85")
    assert isinstance(obj, Constrained)
    assert obj.constraints == (Constraint("utilization", ">=", 0.85),)
    lx = parse_objective("lex:avg_wait,makespan")
    assert isinstance(lx, Lexicographic)
    assert len(lx.levels) == 2


@pytest.mark.parametrize("grammar", [
    "score", "avg_wait", "utilization", "makespan",
    "0.5*avg_wait+0.5*max_slowdown", "2*avg_wait+-1*utilization",
    "lex:avg_wait,makespan", "lex:score,avg_wait,makespan",
    "min:avg_wait@util>=0.85", "min:score@max_wait<=600",
    "min:0.5*avg_wait+0.5*avg_slowdown@utilization>=0.8@max_wait<=600",
    "score:max_wait=0.3:max_slowdown=0.3:avg_wait=0.2:avg_slowdown=0.2",
    # full-precision coefficients/bounds/weights must round-trip too
    # (specs format with repr, not %g's 6 significant digits)
    "0.3333333*avg_wait+0.6666667*max_slowdown",
    "min:avg_wait@util>=0.8512345",
    "score:max_wait=0.12345678:max_slowdown=0.25"
    ":avg_wait=0.25:avg_slowdown=0.25",
])
def test_grammar_round_trips(grammar):
    obj = parse_objective(grammar)
    assert parse_objective(obj.spec) == obj
    assert str(obj) == obj.spec


def test_validate_objective_shared_helper():
    from repro.core.objective import validate_objective
    assert validate_objective("avg_wait") == Weighted(((1.0, "avg_wait"),))
    with pytest.raises(ValueError):
        validate_objective("turnaround")


@pytest.mark.parametrize("bad", [
    "", "nope", "avg_wait@util>0.85", "min:avg_wait@util=0.85",
    "lex:avg_wait", "lex:avg_wait@util>=0.5", "x*avg_wait",
    "score:bogus=1", "0.5*turnaround",
])
def test_grammar_rejects_malformed(bad):
    with pytest.raises((ValueError, SystemExit)):
        parse_objective(bad)


def test_normalize_objective_paths():
    assert normalize_objective(None) is DEFAULT_OBJECTIVE
    obj = parse_objective("avg_wait")
    assert normalize_objective(obj) is obj
    assert normalize_objective("score") == PaperScore()
    with pytest.warns(DeprecationWarning):
        lifted = normalize_objective(PAPER_WEIGHTS)
    assert lifted == PaperScore()
    with pytest.raises(TypeError):
        normalize_objective(3.14)


def test_resolve_goal_rejects_both_spellings():
    with pytest.raises(ValueError, match="not both"):
        resolve_goal("avg_wait", PAPER_WEIGHTS)


def test_registry_extension():
    register_objective("test_tail_goal",
                       lambda: Weighted(((1.0, "max_slowdown"),)))
    assert parse_objective("test_tail_goal") == Weighted(
        ((1.0, "max_slowdown"),))
    with pytest.raises(ValueError, match="already registered"):
        register_objective("test_tail_goal", lambda: PaperScore())


# ----------------------------------------------------------------------
# Bit-exact parity: objective="score" vs the legacy weights path.
# ----------------------------------------------------------------------

@pytest.mark.parametrize("engine", [REF, PAL], ids=["reference", "pallas"])
def test_score_objective_bitwise_parity_both_backends(engine):
    pool = jnp.asarray(EXTENDED_POOL, dtype=jnp.int32)
    for seed in range(8):
        state = make_cluster_state(max_jobs=48, seed=seed,
                                   n_queued=4 + seed * 2, n_running=seed % 4)
        d_obj = engine.decide(state, pool, "score")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            d_leg = engine.decide(state, pool, weights=PAPER_WEIGHTS)
        assert int(d_obj.policy_index) == int(d_leg.policy_index)
        np.testing.assert_array_equal(np.asarray(d_obj.costs),
                                      np.asarray(d_leg.costs))
        np.testing.assert_array_equal(np.asarray(d_obj.run_mask),
                                      np.asarray(d_leg.run_mask))
        # and both match the raw policy_cost arithmetic on the metrics
        # (allclose: recomputing eagerly outside the jitted decide can
        # differ in the last ulp through XLA fusion)
        raw = policy_cost(d_obj.metrics, PAPER_WEIGHTS)
        raw = jnp.where(d_obj.deadlocked, jnp.inf, raw)
        np.testing.assert_allclose(np.asarray(d_obj.costs),
                                   np.asarray(raw), rtol=1e-6)


def test_weights_kwarg_warns_and_matches_via_whatif():
    state = make_cluster_state(seed=3)
    pool = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    d_obj = whatif.decide(state, pool)
    with pytest.warns(DeprecationWarning):
        d_leg = whatif.decide(state, pool, weights=ScoreWeights())
    np.testing.assert_array_equal(np.asarray(d_obj.costs),
                                  np.asarray(d_leg.costs))


def test_cost_terms_breakdown_sums_to_costs():
    state = make_cluster_state(seed=9)
    pool = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    d = whatif.decide(state, pool, "score")
    assert set(d.cost_terms) == {"max_wait", "max_slowdown",
                                 "avg_wait", "avg_slowdown"}
    total = sum(np.asarray(v) for v in d.cost_terms.values())
    live = ~np.asarray(d.deadlocked)
    np.testing.assert_allclose(total[live],
                               np.asarray(d.costs)[live], rtol=1e-6)


def test_single_metric_objective_selects_its_metric():
    state = make_cluster_state(seed=11)
    pool = jnp.asarray(EXTENDED_POOL, dtype=jnp.int32)
    d = whatif.decide(state, pool, "avg_wait")
    aw = np.where(np.asarray(d.deadlocked), np.inf,
                  np.asarray(d.metrics.avg_wait))
    assert int(d.policy_index) == int(np.argmin(aw))
    np.testing.assert_array_equal(np.asarray(d.costs)[~np.isinf(aw)],
                                  aw[~np.isinf(aw)])


def test_ensemble_accepts_objective():
    import jax
    state = make_cluster_state(seed=5)
    pool = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    d = whatif.decide_ensemble(state, pool, jax.random.PRNGKey(0),
                               n_ens=2, noise=0.1, objective="avg_wait")
    assert d.costs.shape == (3,)
    assert set(d.cost_terms) == {"avg_wait"}


# ----------------------------------------------------------------------
# Constrained goals: feasibility fallback semantics.
# ----------------------------------------------------------------------

def test_constrained_feasible_beats_infeasible_primary():
    # candidate 0: better primary but infeasible; candidate 1: feasible
    obj = parse_objective("min:avg_wait@util>=0.85")
    m = _metrics(avg_wait=[1.0, 100.0], utilization=[0.5, 0.9])
    c = np.asarray(obj.costs(m))
    assert c[1] < c[0]


def test_constrained_all_infeasible_least_violation_wins():
    obj = parse_objective("min:avg_wait@util>=0.85")
    # all below the bound; candidate 2 violates least despite the worst
    # primary — the fallback ranks by violation first
    m = _metrics(avg_wait=[1.0, 2.0, 300.0],
                 utilization=[0.2, 0.5, 0.80])
    assert int(np.argmin(np.asarray(obj.costs(m)))) == 2


def test_constrained_among_feasible_primary_decides():
    obj = parse_objective("min:avg_wait@util>=0.5")
    m = _metrics(avg_wait=[30.0, 10.0, 20.0],
                 utilization=[0.9, 0.6, 0.95])
    assert int(np.argmin(np.asarray(obj.costs(m)))) == 1


def test_constrained_multiple_constraints_sum_violations():
    obj = parse_objective("min:avg_wait@util>=0.8@max_wait<=100")
    # 0 violates both slightly; 1 violates one badly; 2 feasible
    m = _metrics(avg_wait=[1.0, 1.0, 50.0],
                 utilization=[0.75, 0.9, 0.85],
                 max_wait=[110.0, 400.0, 90.0])
    c = np.asarray(obj.costs(m))
    assert int(np.argmin(c)) == 2
    assert c[0] < c[1]          # 10.05 total violation < 300


def test_constrained_ties_break_by_pool_position():
    obj = parse_objective("min:avg_wait@util>=0.5")
    m = _metrics(avg_wait=[10.0, 10.0], utilization=[0.6, 0.6])
    c = np.asarray(obj.costs(m))
    assert c[0] == c[1]          # argmin downstream picks index 0


# ----------------------------------------------------------------------
# Lexicographic goals.
# ----------------------------------------------------------------------

def test_lex_tie_broken_by_second_level():
    obj = parse_objective("lex:avg_wait,makespan")
    m = _metrics(avg_wait=[5.0, 5.0, 6.0], makespan=[200.0, 100.0, 1.0])
    c = np.asarray(obj.costs(m))
    assert int(np.argmin(c)) == 1
    assert c[2] > c[0]           # worse primary loses despite makespan


def test_lex_primary_dominates():
    obj = parse_objective("lex:avg_wait,makespan")
    m = _metrics(avg_wait=[1.0, 2.0], makespan=[1e9, 0.0])
    assert int(np.argmin(np.asarray(obj.costs(m)))) == 0


# ----------------------------------------------------------------------
# Per-objective selection over a replay grid.
# ----------------------------------------------------------------------

def _grid(S=3, n_jobs=14, seed=0):
    from repro.cluster.workload import poisson_trace, stack_scenarios
    traces = [poisson_trace(n_jobs, 32, 8.0, (1, 16), (30.0, 900.0),
                            seed=seed + s) for s in range(S)]
    return stack_scenarios(traces, 32)


def test_replay_grid_selects_per_objective():
    scen = _grid()
    pool = parse_pool("extended")
    out_aw = REF.replay_grid(scen, pool.spec, "avg_wait")
    out_ut = REF.replay_grid(scen, pool.spec, "utilization")
    assert out_aw.costs.shape == out_aw.deadlocked.shape
    dead = np.asarray(out_aw.deadlocked)
    aw = np.where(dead, np.inf, np.asarray(out_aw.metrics.avg_wait))
    np.testing.assert_array_equal(np.asarray(out_aw.best),
                                  np.argmin(aw, axis=1))
    ut = np.where(dead, -np.inf, np.asarray(out_ut.metrics.utilization))
    np.testing.assert_array_equal(np.asarray(out_ut.best),
                                  np.argmax(ut, axis=1))
    # replay times themselves are objective-independent
    np.testing.assert_array_equal(np.asarray(out_aw.end_t),
                                  np.asarray(out_ut.end_t))


def test_replay_grid_default_objective_is_score():
    scen = _grid(S=2)
    pool = parse_pool("paper")
    out = REF.replay_grid(scen, pool.spec)
    # expected costs under jit, like the replay's own selection: eager
    # op-by-op arithmetic misses XLA's FMA contraction (DESIGN.md §9)
    # and lands 1 ulp away
    costs = jax.jit(lambda m, dead: jnp.where(
        dead, jnp.inf, policy_cost(m, PAPER_WEIGHTS)))(
            out.metrics, out.deadlocked)
    np.testing.assert_array_equal(np.asarray(out.costs),
                                  np.asarray(costs))
    np.testing.assert_array_equal(np.asarray(out.best),
                                  np.argmin(np.asarray(costs), axis=1))


def test_single_scenario_replay_best_scalar():
    scen = _grid(S=1)
    out = REF.replay(scen, parse_pool("extended").spec, "makespan")
    assert out.costs.shape == (7,)
    assert out.best.shape == ()
    ms = np.where(np.asarray(out.deadlocked), np.inf,
                  np.asarray(out.metrics.makespan))
    assert int(out.best) == int(np.argmin(ms))


def test_sharded_replay_grid_objective(mesh11):
    from repro.core.whatif import sharded_replay_grid
    scen = _grid(S=2)
    pool = parse_pool("extended")
    fn = sharded_replay_grid(mesh11, "data", objective="avg_wait")
    out = fn(scen, pool)
    ref = REF.replay_grid(scen, pool.spec, "avg_wait")
    np.testing.assert_array_equal(np.asarray(out.best),
                                  np.asarray(ref.best))
    np.testing.assert_array_equal(np.asarray(out.costs),
                                  np.asarray(ref.costs))


# ----------------------------------------------------------------------
# Host-side report scoring + config/emulator surfaces.
# ----------------------------------------------------------------------

def test_report_costs_matches_device_semantics():
    rows = [
        {"avg_wait": 10.0, "max_wait": 60.0, "avg_slowdown": 2.0,
         "max_slowdown": 4.0, "makespan": 100.0, "utilization": 0.8},
        {"avg_wait": 20.0, "max_wait": 120.0, "avg_slowdown": 3.0,
         "max_slowdown": 6.0, "makespan": 90.0, "utilization": 0.9},
    ]
    m = metrics_from_rows(rows)
    np.testing.assert_allclose(
        report_costs("score", rows),
        np.asarray(policy_cost(m, PAPER_WEIGHTS)))
    np.testing.assert_allclose(report_costs("utilization", rows),
                               [-0.8, -0.9])


def test_twin_config_objective_and_legacy_weights():
    from repro.configs.schedtwin import TwinConfig
    assert TwinConfig().make_objective() == PaperScore()
    cfg = TwinConfig(objective="min:avg_wait@util>=0.85")
    assert isinstance(cfg.make_objective(), Constrained)
    with pytest.warns(DeprecationWarning):
        legacy = TwinConfig(weights=ScoreWeights(0.5, 0.5, 0.0, 0.0)
                            ).make_objective()
    assert legacy == PaperScore(ScoreWeights(0.5, 0.5, 0.0, 0.0))


def test_emulator_run_stamps_objective():
    from repro.cluster.emulator import ClusterEmulator
    from repro.cluster.workload import poisson_trace
    trace = poisson_trace(12, 32, 8.0, (1, 16), (30.0, 900.0), seed=1)
    rep = ClusterEmulator(trace, 32).run(policy_id=1, fast=True,
                                         objective="avg_wait")
    assert rep.objective == "avg_wait"
    np.testing.assert_allclose(rep.objective_cost, rep.avg_wait,
                               rtol=1e-5)
    assert set(rep.objective_terms) == {"avg_wait"}
    rep2 = ClusterEmulator(trace, 32).run(policy_id=1, fast=True)
    assert rep2.objective is None
    # rank-based goals have no scalar cost for a lone run (a single
    # candidate's composed rank is identically 0) — terms only
    rep3 = ClusterEmulator(trace, 32).run(
        policy_id=1, fast=True, objective="min:avg_wait@util>=0.9")
    assert rep3.objective_cost is None
    assert "violation:utilization>=0.9" in rep3.objective_terms
    assert rep3.objective_terms["violation:utilization>=0.9"] > 0.0


def test_twin_records_objective_telemetry():
    from repro.cluster.emulator import ClusterEmulator
    from repro.cluster.workload import poisson_trace
    from repro.core.events import EventBus
    from repro.core.twin import SchedTwin
    trace = poisson_trace(12, 16, 6.0, (1, 8), (30.0, 300.0), seed=2)
    bus = EventBus()
    em = ClusterEmulator(trace, 16, bus=bus)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                     max_jobs=em.max_jobs, pool="paper",
                     objective="min:avg_wait@util>=0.5",
                     free_nodes_probe=lambda: em.free_nodes)
    em.run(on_event=twin.pump)
    assert twin.telemetry.cycles
    rec = twin.telemetry.cycles[0]
    assert rec.objective == "min:avg_wait@utilization>=0.5"
    assert set(rec.term_costs) == {"WFP", "FCFS", "SJF"}
    for terms in rec.term_costs.values():
        assert "avg_wait" in terms
        assert "violation:utilization>=0.5" in terms
    breakdown = twin.telemetry.objective_breakdown()
    assert set(breakdown) == {"WFP", "FCFS", "SJF"}
