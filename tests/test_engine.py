"""DrainEngine: backend parity + batched-drain equivalence (DESIGN.md).

The contract under test:

* ``pallas`` (interpret mode on CPU) and ``reference`` backends yield
  BIT-IDENTICAL decisions — run_mask, winner, costs, drain metrics —
  across random snapshots over the EXTENDED_POOL;
* the batched drain is bit-for-bit the stack of k scalar drains
  (``jax.vmap(simulate_to_drain)``), per-fork freeze semantics
  included;
* the emulator's static baseline is backend-independent;
* ``whatif.pool_array`` preserves the caller's tie-break order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import whatif
from repro.core.des import simulate_to_drain
from repro.core.engine import DrainEngine
from repro.core.policies import EXTENDED_POOL, FCFS, PAPER_POOL, SJF, WFP

from conftest import make_cluster_state

REF = DrainEngine("reference")
PAL = DrainEngine("pallas", interpret=True)

N_SNAPSHOTS = 60  # acceptance: >= 50 random snapshots
MAX_JOBS = 48     # fixed shape -> one compile per backend


def _snapshots():
    for seed in range(N_SNAPSHOTS):
        yield make_cluster_state(
            max_jobs=MAX_JOBS, total_nodes=32, seed=seed,
            n_queued=4 + seed % 16, n_running=seed % 5,
            now=100.0 + 37.0 * seed)


def _assert_decisions_identical(da, db, ctx=""):
    assert int(da.policy_index) == int(db.policy_index), ctx
    np.testing.assert_array_equal(np.asarray(da.run_mask),
                                  np.asarray(db.run_mask), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(da.costs),
                                  np.asarray(db.costs), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(da.deadlocked),
                                  np.asarray(db.deadlocked), err_msg=ctx)
    for field, a, b in zip(da.metrics._fields, da.metrics, db.metrics):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{ctx} metric={field}")


def test_backend_parity_extended_pool_random_snapshots():
    pool = jnp.asarray(EXTENDED_POOL, dtype=jnp.int32)
    for i, state in enumerate(_snapshots()):
        d_ref = REF.decide(state, pool)
        d_pal = PAL.decide(state, pool)
        _assert_decisions_identical(d_ref, d_pal, ctx=f"snapshot {i}")


def test_batched_drain_matches_vmapped_scalar():
    pool = jnp.asarray(EXTENDED_POOL, dtype=jnp.int32)
    vmapped = jax.jit(jax.vmap(simulate_to_drain, in_axes=(None, 0)))
    for seed in (0, 7, 23, 41):
        state = make_cluster_state(max_jobs=MAX_JOBS, seed=seed,
                                   n_queued=12, n_running=3)
        res_b = REF.drain(state, pool)
        res_v = vmapped(state, pool)
        eq = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)),
                          res_b.state, res_v.state)
        assert jax.tree.all(eq), f"seed {seed}: state diverged"
        np.testing.assert_array_equal(np.asarray(res_b.first_started),
                                      np.asarray(res_v.first_started))
        np.testing.assert_array_equal(np.asarray(res_b.deadlocked),
                                      np.asarray(res_v.deadlocked))
        np.testing.assert_array_equal(np.asarray(res_b.iters),
                                      np.asarray(res_v.iters))


def test_batched_drain_deadlock_detected_and_rest_scheduled():
    """Deadlock is policy-independent (req > total nodes), so both
    forks flag it — after scheduling whatever still fits."""
    from repro.core.state import add_job, empty_state
    state = empty_state(16, 8)
    state = add_job(state, 0, 0.0, 9, 100.0)   # 9 > 8: can never fit
    state = add_job(state, 1, 1.0, 2, 50.0)
    pool = jnp.asarray([FCFS, SJF], dtype=jnp.int32)
    res = REF.drain(state, pool)
    dead = np.asarray(res.deadlocked)
    assert dead[0] and dead[1]
    # ... but job 1 still ran in both forks before the deadlock
    assert float(res.state.jobs.start_t[0][1]) >= 0
    assert float(res.state.jobs.start_t[1][1]) >= 0


def test_batched_drain_freezes_finished_fork_while_others_step():
    """The per-fork freeze path proper: forks that need different
    event counts share one while_loop — the early finisher must freeze
    (bit-identical to its scalar drain) while the slow fork keeps
    stepping.  On 4 nodes with A(2n, 10s), B(2n, 30s), C(4n, 5s):
    FCFS packs A+B first and needs 3 events to drain; SJF starts C
    alone and finishes in 2."""
    from repro.core.state import add_job, empty_state
    state = empty_state(16, 4)
    state = add_job(state, 0, 0.0, 2, 10.0)
    state = add_job(state, 1, 1.0, 2, 30.0)
    state = add_job(state, 2, 2.0, 4, 5.0)
    state = state._replace(now=jnp.float32(3.0))
    pool = jnp.asarray([FCFS, SJF], dtype=jnp.int32)
    res = REF.drain(state, pool)
    assert list(np.asarray(res.iters)) == [3, 2]
    assert not np.asarray(res.deadlocked).any()
    for i, pid in enumerate((FCFS, SJF)):
        scalar = simulate_to_drain(state, jnp.int32(pid))
        eq = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y[i])),
                          scalar.state, res.state)
        assert jax.tree.all(eq), f"fork {i} diverged from scalar drain"
        assert int(scalar.iters) == int(np.asarray(res.iters)[i])


def test_engine_matches_legacy_vmap_decide():
    pool = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    for seed in (2, 13):
        state = make_cluster_state(max_jobs=MAX_JOBS, seed=seed)
        _assert_decisions_identical(
            REF.decide(state, pool),
            whatif.decide_legacy_vmap(state, pool),
            ctx=f"legacy seed {seed}")


def test_ensemble_rides_batch_axis_both_backends():
    state = make_cluster_state(max_jobs=MAX_JOBS, seed=5)
    pool = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    key = jax.random.PRNGKey(1)
    d_ref = REF.decide_ensemble(state, pool, key, n_ens=3, noise=0.25)
    d_pal = PAL.decide_ensemble(state, pool, key, n_ens=3, noise=0.25)
    _assert_decisions_identical(d_ref, d_pal, ctx="ensemble")


def test_emulator_static_baseline_backend_independent():
    from repro.cluster.emulator import ClusterEmulator
    from repro.cluster.workload import JobSpec
    rng = np.random.default_rng(0)
    trace = [JobSpec(j, j * 4.0, int(rng.integers(1, 12)),
                     float(rng.uniform(30, 300)),
                     float(rng.uniform(20, 280)), "t")
             for j in range(24)]
    reports = {}
    for eng in (REF, PAL):
        reports[eng.backend] = ClusterEmulator(
            trace, 16, engine=eng, check_invariants=True).run(policy_id=WFP)
    np.testing.assert_array_equal(reports["reference"].start_t,
                                  reports["pallas"].start_t)
    np.testing.assert_array_equal(reports["reference"].end_t,
                                  reports["pallas"].end_t)


def test_twin_runs_on_pallas_engine():
    from repro.cluster.emulator import ClusterEmulator
    from repro.cluster.workload import JobSpec
    from repro.core.events import EventBus
    from repro.core.twin import SchedTwin
    rng = np.random.default_rng(1)
    trace = [JobSpec(j, j * 6.0, int(rng.integers(1, 8)),
                     float(rng.uniform(30, 200)),
                     float(rng.uniform(20, 180)), "t")
             for j in range(12)]
    bus = EventBus()
    em = ClusterEmulator(trace, 16, bus=bus, engine=PAL)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=16,
                     max_jobs=em.max_jobs, engine=PAL)
    report = em.run(on_event=twin.pump)
    assert report.n_jobs == len(trace)


def test_config_backend_knob():
    from repro.configs.schedtwin import PALLAS_TWIN, PAPER_TWIN
    assert PAPER_TWIN.make_engine() == DrainEngine("reference")
    assert PALLAS_TWIN.make_engine().backend == "pallas"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown pass backend"):
        DrainEngine("cuda")


def test_pool_array_preserves_caller_order():
    """Regression: pool_array used to sort ids, discarding the caller's
    tie-break priority (position = priority for select_policy)."""
    ids = [SJF, WFP, FCFS]
    arr = np.asarray(whatif.pool_array(ids))
    assert list(arr) == ids
    assert arr.dtype == np.int32


# ----------------------------------------------------------------------
# Every producer's outputs, bit for bit: SHA-256 digests of their bytes
# on one fixed CPU state and scenario set (J = 64, pool ``paper``, goal
# ``score``, reference backend), recorded before the decision and replay
# programs were folded into one member program and one block streamer.
# ----------------------------------------------------------------------

DIGEST_J = 64
DIGEST_POOL = "paper"
DIGEST_FAN = dict(n=4, runtime_noise=0.3, burst_amplitude=0.3,
                  burst_period=600.0, failure_prob=0.4, failure_domains=4,
                  seed=7)
DIGEST_RACE_FAN = dict(n=8, runtime_noise=0.3, failure_prob=0.4,
                       failure_domains=4, seed=7)

PRODUCER_DIGESTS = {
    "decide":
        "dbaf54d1188305204240deca23247f7a0b62567271b2bb8e7e0b872240e1c970",
    # recorded with member 0 exact, after the ensemble's fix (its
    # estimates used to be scaled by exp(-noise**2 / 2))
    "decide_ensemble":
        "71312b3dd196bc0be8cfff49082e9c9ffe082579a94c57ffbfcbd02896d533a7",
    "decide_fan":
        "3f9816da1285f143b125e7e717ffffc55e0943b1b97eb0b438691285a9999c0f",
    "decide_race":
        "5eeaedd125ee6685731e6698d3b8870cdb43ff6b4a2da3d067d2ad2da90447f7",
    "sharded_whatif":
        "dbaf54d1188305204240deca23247f7a0b62567271b2bb8e7e0b872240e1c970",
    "replay_grid":
        "d2495ad7ffb8ea97a30777a3d86c896ed5233ea50dbf0df371ce1de460087b53",
    "fan_grid":
        "c126765bfd3385ade8ae78ebf9edcfb035770f78c80d5519758c121cfae2989f",
    "fan_window_grid":
        "6ab0b018f8c0ee49f5c5211759e8de20c7901df1eb102b20b5f459ba006c809c",
    "sharded_replay_grid":
        "e0b53d30f675d4218b8a3c0b75d519b78231b0f83b40c471464f2835341cabff",
    "sharded_fan_grid":
        "76a1482f8663654f76b7a409ea72fbafc3a153705a209ae7ad1505265610170c",
    "sharded_race_grid":
        "a832ad04628f0f1e97670012c87d659d1eb6f2f4f67f0f0639ea9d55b4e71ced",
}


def _digest(*arrays) -> str:
    import hashlib
    h = hashlib.sha256()
    for a in jax.tree.leaves(arrays):
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _produce(name: str) -> str:
    from repro.cluster.workload import (bursty_trace, poisson_trace,
                                        stack_scenarios)
    from repro.core.fan import FanSpec
    from repro.core.policies import parse_pool
    from repro.core.race import RaceSpec
    from repro.launch.mesh import make_mesh

    pool = parse_pool(DIGEST_POOL).spec
    spec = FanSpec(**DIGEST_FAN)
    race = RaceSpec(fan=FanSpec(**DIGEST_RACE_FAN), f0=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    state = make_cluster_state(max_jobs=DIGEST_J, total_nodes=32,
                               n_queued=14, n_running=4, seed=3)

    def scenarios(S):
        return stack_scenarios(
            [(bursty_trace if i % 2 else poisson_trace)(
                24, 16, 6.0 + i, (1, 12), (30.0, 400.0), seed=40 + i)
             for i in range(S)], 16, max_jobs=DIGEST_J)

    def grid(o):
        return _digest(o.start_t, o.end_t, o.metrics, o.deadlocked,
                       o.events, o.costs, o.best)

    def fan(o):
        return _digest(o.start_t, o.end_t, o.metrics, o.deadlocked,
                       o.events, o.member_costs, o.costs, o.best,
                       o.cost_ci, o.fan_width)

    if name == "decide":
        return _digest(REF.decide(state, pool, "score").words)
    if name == "decide_ensemble":
        return _digest(REF.decide_ensemble(
            state, pool, jax.random.PRNGKey(5), n_ens=4, noise=0.3,
            objective="score").words)
    if name == "decide_fan":
        return _digest(REF.decide_fan(state, pool, spec, "score").words)
    if name == "decide_race":
        d, r = REF.decide_race(state, pool, race, "score")
        return _digest(d.words, r.member_costs, r.costs, r.best, r.cost_ci,
                       r.fan_width, r.keep)
    if name == "sharded_whatif":
        return _digest(whatif.sharded_whatif(
            mesh, engine=REF, objective="score")(state, pool).words)
    if name == "replay_grid":
        return grid(REF.replay_grid(scenarios(3), pool, "score"))
    if name == "fan_grid":
        return fan(REF.fan_grid(scenarios(3), pool, spec, "score"))
    if name == "fan_window_grid":
        return fan(REF.fan_window_grid(scenarios(3), pool, spec, "score",
                                       lo=1, width=2))
    if name == "sharded_replay_grid":      # blocks of 2, 2 and a tail of 1
        o = whatif.sharded_replay_grid(mesh, engine=REF, objective="score",
                                       block_size=2)(scenarios(5), pool)
        return _digest(o.start_t, o.end_t, o.costs, o.best)
    if name == "sharded_fan_grid":         # 12 fan rows in blocks of 5
        o = whatif.sharded_fan_grid(mesh, engine=REF, objective="score",
                                    fan=spec, block_size=5)(scenarios(3),
                                                            pool)
        return _digest(o.start_t, o.end_t, o.member_costs, o.costs,
                       o.best)
    assert name == "sharded_race_grid"
    r = whatif.sharded_race_grid(mesh, engine=REF, objective="score",
                                 race=race, block_size=3)(scenarios(3), pool)
    return _digest(r.member_costs, r.costs, r.best, r.cost_ci, r.fan_width,
                   r.keep, r.members, r.fan_size, r.separation)


@pytest.mark.parametrize("name", sorted(PRODUCER_DIGESTS))
def test_producer_outputs_match_recorded_bits(name):
    assert _produce(name) == PRODUCER_DIGESTS[name], name


def test_ensemble_member0_is_the_nominal_pass():
    """The ensemble's qrun set comes from member 0, which must be the
    mirror's own estimates.  Here EASY backfill hinges on an estimate:
    job 2 (4 nodes, estimate 52 s at t=50) may start only if it ends
    by the head's shadow time, 100 s; any shrink of its estimate by
    more than 4 % (``exp(-noise**2 / 2)`` is 0.956 at noise 0.3) would
    backfill it.  So the run mask must be the chosen policy's nominal
    first pass, which starts nothing."""
    from repro.core.policies import parse_pool
    from repro.core.state import add_job, empty_state, start_job
    state = empty_state(8, 10)
    state = add_job(state, 0, 0.0, 6, 100.0)
    state = start_job(state, 0, 0.0)             # ends at t=100
    state = add_job(state, 1, 1.0, 8, 50.0)      # the head: waits to t=100
    state = add_job(state, 2, 2.0, 4, 52.0)
    state = state._replace(now=jnp.float32(50.0))
    pool = parse_pool("paper").spec
    for eng in (REF, PAL):
        d = eng.decide_ensemble(state, pool, jax.random.PRNGKey(0),
                                n_ens=4, noise=0.3)
        chosen = jax.tree.map(lambda x: x[int(d.policy_index)], pool)
        nominal = eng.schedule_pass_starts(state, chosen)
        np.testing.assert_array_equal(np.asarray(d.run_mask),
                                      np.asarray(nominal), err_msg=eng.backend)
        assert not np.asarray(nominal).any()
