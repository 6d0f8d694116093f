"""A decision's one-buffer host view (``Decision.words``): what every
producer packs in its own program reads back on the host bit for bit as
the decision's fields, whatever the goal's terms, the table's width or
the decision mode.  And the mirror's one-buffer upload
(``PackedState``): what ``sync.pack`` writes on the host unpacks in the
decision's program bit for bit as the mirror, and decides as it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sync, whatif
from repro.core.engine import DrainEngine
from repro.core.events import Event, EventBus, EventKind
from repro.core.fan import FanSpec
from repro.core.objective import resolve_goal
from repro.core.policies import PAPER_POOL, parse_pool
from repro.core.race import RaceSpec
from repro.core.state import (INVALID, PackedState, SimState, add_job,
                              as_state)
from repro.core.twin import SchedTwin

from conftest import gathered_mirror, make_cluster_state

REF = DrainEngine("reference")
POOL = parse_pool("paper").spec
NODES = 32

GOALS = {"score": "score", "one_term": "avg_wait",
         "distributional": "p95:avg_wait"}


def _twin(capacity):
    return SchedTwin(bus=EventBus(), qrun=lambda ids, t: None,
                     total_nodes=NODES, max_jobs=capacity)


def _queue(twin, rng, j, t, nodes=None):
    nodes = rng.integers(1, 5) if nodes is None else nodes
    twin._apply(Event(EventKind.QUEUEJOB, t, j, {
        "nodes": float(nodes),
        "est_runtime": float(rng.uniform(60.0, 900.0))}), True)


def _recycled_table(capacity=1024):
    """A host mirror and its slot map from a job table whose slots were
    recycled: 24 jobs queued, 8 of them run and ended, 20 more with ids
    far past the table taking the freed slots, 4 of those running."""
    twin = _twin(capacity)
    rng = np.random.default_rng(5)
    for j in range(24):
        _queue(twin, rng, j, float(j))
    for j in range(8):
        twin._apply(Event(EventKind.RUNJOB, 30.0, j), True)
    for j in range(8):
        twin._apply(Event(EventKind.JOBOBIT, 100.0 + j, j), True)
    for j in range(3000, 3020):
        _queue(twin, rng, j, 110.0 + j - 3000)
    for j in range(3000, 3004):
        twin._apply(Event(EventKind.RUNJOB, 140.0, j), True)
    assert twin._meter.tally["recycled"] == 8
    _, ids = sync.pack(twin.state, twin.slots)
    assert set(range(3000, 3020)) <= set(ids.tolist())
    return twin.state, twin.slots


def _table_of(state):
    """A device ``SimState`` whose row i is job i, as a host mirror and
    its slot map."""
    mirror = jax.tree.map(np.asarray, state)
    ids = np.where(mirror.jobs.state != INVALID,
                   np.arange(mirror.jobs.capacity), -1)
    return mirror, sync.JobSlots.from_ids(ids)


def _full_late_table(capacity=256):
    """Every node taken and the clock near 1.4e6 s, where float32 times
    are 0.125 s apart (a Polaris-scale stream's): 6 jobs running on all
    32 nodes, 10 queued behind them."""
    twin = _twin(capacity)
    rng = np.random.default_rng(9)
    t0 = 1.4e6 + 0.3
    running = (8, 8, 4, 4, 4, 4)
    for j in range(16):
        _queue(twin, rng, 9000 + j, t0 + 7.7 * j,
               running[j] if j < len(running) else None)
    for j in range(len(running)):
        twin._apply(Event(EventKind.RUNJOB, t0 + 130.1 + j, 9000 + j), True)
    assert int(twin.state.free_nodes) == 0
    return twin.state, twin.slots


TABLES = {
    "J256": lambda: _table_of(make_cluster_state(
        max_jobs=256, total_nodes=NODES, n_queued=14, n_running=2, seed=0)),
    "recycled1024": _recycled_table,
}

#: Tables for the round trip alone: on the last no job can start.
ROUND_TRIP = {**TABLES, "full_late256": _full_late_table}


def _state(table) -> SimState:
    """``table``'s mirror as the device decides over it: a ``SimState``
    gathered row by row in the test."""
    return jax.device_put(gathered_mirror(*TABLES[table]())[0])


def _upload(table) -> PackedState:
    """``table``'s mirror as the twin uploads it: one buffer."""
    return jax.device_put(PackedState(sync.pack(*TABLES[table]())[0]))


PRODUCERS = {
    "decide": lambda st, goal: REF.decide(st, POOL, goal),
    "decide_ensemble": lambda st, goal: REF.decide_ensemble(
        st, POOL, jax.random.PRNGKey(1), n_ens=3, objective=goal),
    "decide_fan": lambda st, goal: REF.decide_fan(
        st, POOL, FanSpec(n=4, runtime_noise=0.3, failure_prob=0.2, seed=3),
        objective=goal),
    "decide_race": lambda st, goal: REF.decide_race(
        st, POOL, RaceSpec(fan=FanSpec(n=8, runtime_noise=0.3, seed=3),
                           f0=2), objective=goal)[0],
    "sharded_whatif": lambda st, goal: whatif.sharded_whatif(
        _MESH[0], engine=REF, objective=goal)(st, POOL),
}

_MESH = []


@pytest.fixture(autouse=True)
def _mesh(mesh11):
    _MESH[:] = [mesh11]


def _bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).view(np.int32)


def assert_view_is_fields(d):
    """``d.words`` read to the host equals each field read on its own."""
    words = np.asarray(d.words)
    assert words.dtype == np.int32
    view = d.unpack(words)
    np.testing.assert_array_equal(view.run_mask, np.asarray(d.run_mask))
    assert view.run_mask.dtype == bool
    assert view.policy_index == int(d.policy_index)
    np.testing.assert_array_equal(view.costs.view(np.int32), _bits(d.costs))
    terms = d.cost_terms or {}
    assert list(view.cost_terms) == list(terms)
    for name, v in terms.items():
        np.testing.assert_array_equal(view.cost_terms[name].view(np.int32),
                                      _bits(v), err_msg=name)
    for field in ("cost_ci", "fan_width"):
        got, want = getattr(view, field), getattr(d, field)
        assert (got is None) == (want is None), field
        if want is not None:
            np.testing.assert_array_equal(got.view(np.int32), _bits(want),
                                          err_msg=field)
    return view


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("goal", sorted(GOALS))
@pytest.mark.parametrize("producer", sorted(PRODUCERS))
def test_words_read_back_as_the_fields(producer, goal, table):
    state = _state(table)
    d = PRODUCERS[producer](state, resolve_goal(GOALS[goal]))
    view = assert_view_is_fields(d)
    assert view.run_mask.shape == (state.jobs.capacity,)
    assert view.costs.shape == (3,)
    assert view.run_mask.any()
    if goal == "score":
        assert len(view.cost_terms) == 4
    if producer in ("decide_ensemble", "decide_fan", "decide_race"):
        assert view.cost_ci is not None and view.fan_width is not None


@pytest.mark.parametrize("table", sorted(TABLES))
def test_legacy_vmap_words_hold_no_terms(table):
    d = whatif.decide_legacy_vmap(_state(table),
                                  jnp.asarray(PAPER_POOL, jnp.int32))
    view = assert_view_is_fields(d)
    assert view.cost_terms == {} and view.cost_ci is None
    assert np.asarray(d.words).shape == (d.run_mask.shape[0] + 1 + 3,)


@pytest.mark.parametrize("producer", ["decide", "decide_fan"])
def test_a_deadlocked_fork_crosses_as_inf(producer):
    state = _state("J256")
    free = int(np.flatnonzero(np.asarray(state.jobs.state) == INVALID)[0])
    # one more queued job, wider than the cluster: no fork can drain
    state = add_job(state, free, 1.0, NODES + 1, 60.0)
    d = PRODUCERS[producer](state, resolve_goal("score"))
    assert np.asarray(d.deadlocked).all()
    view = assert_view_is_fields(d)
    assert np.isposinf(view.costs).all()
    if view.cost_ci is not None:
        assert np.isposinf(view.cost_ci).all()


def test_replacing_a_field_packs_the_words_anew():
    d = REF.decide(_state("J256"), POOL)
    mask = np.asarray(d.run_mask).copy()
    mask[np.flatnonzero(mask)[0]] = False
    altered = d._replace(run_mask=jnp.asarray(mask))
    np.testing.assert_array_equal(
        altered.unpack(np.asarray(altered.words)).run_mask, mask)
    assert d._replace(fan_size=4).words is d.words


def _leaves_bits(state):
    return [(np.asarray(x).dtype, np.asarray(x).shape,
             np.asarray(x).view(np.int32).tolist())
            for x in jax.tree.leaves(state)]


@pytest.mark.parametrize("table", sorted(ROUND_TRIP))
def test_a_packed_mirror_unpacks_bit_for_bit(table):
    mirror, slots = ROUND_TRIP[table]()
    words, ids = sync.pack(mirror, slots)
    want, want_ids = gathered_mirror(mirror, slots)
    J = mirror.jobs.capacity
    assert words.dtype == np.int32 and words.shape == (6 * J + 3,)
    np.testing.assert_array_equal(ids, want_ids)
    for unpack in (as_state, jax.jit(as_state)):
        got = unpack(PackedState(jnp.asarray(words)))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert _leaves_bits(got) == _leaves_bits(want)
    st = jax.device_put(want)
    assert as_state(st) is st


#: Every producer the twin runs on its packed upload.
PACKED_PRODUCERS = ("decide", "decide_ensemble", "decide_fan", "decide_race")


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("goal", sorted(GOALS))
@pytest.mark.parametrize("producer", PACKED_PRODUCERS)
def test_a_packed_upload_decides_as_its_state(producer, goal, table):
    goal = resolve_goal(GOALS[goal])
    packed = PRODUCERS[producer](_upload(table), goal)
    plain = PRODUCERS[producer](_state(table), goal)
    np.testing.assert_array_equal(np.asarray(packed.words),
                                  np.asarray(plain.words))
    assert packed.fan_size == plain.fan_size
