"""A decision's one-buffer host view (``Decision.words``): what every
producer packs in its own program reads back on the host bit for bit as
the decision's fields, whatever the goal's terms, the table's width or
the decision mode."""
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
from repro.core.state import INVALID, add_job
from repro.core.twin import SchedTwin

from conftest import make_cluster_state

REF = DrainEngine("reference")
POOL = parse_pool("paper").spec
NODES = 32

GOALS = {"score": "score", "one_term": "avg_wait",
         "distributional": "p95:avg_wait"}


def _recycled_table(capacity=1024):
    """A packed mirror from a job table whose slots were recycled: 24
    jobs queued, 8 of them run and ended, 20 more with ids far past the
    table taking the freed slots, 4 of those running."""
    twin = SchedTwin(bus=EventBus(), qrun=lambda ids, t: None,
                     total_nodes=NODES, max_jobs=capacity)
    rng = np.random.default_rng(5)

    def queue(j, t):
        twin._apply(Event(EventKind.QUEUEJOB, t, j, {
            "nodes": float(rng.integers(1, 5)),
            "est_runtime": float(rng.uniform(60.0, 900.0))}), True)

    for j in range(24):
        queue(j, float(j))
    for j in range(8):
        twin._apply(Event(EventKind.RUNJOB, 30.0, j), True)
    for j in range(8):
        twin._apply(Event(EventKind.JOBOBIT, 100.0 + j, j), True)
    for j in range(3000, 3020):
        queue(j, 110.0 + j - 3000)
    for j in range(3000, 3004):
        twin._apply(Event(EventKind.RUNJOB, 140.0, j), True)
    assert twin._meter.tally["recycled"] == 8
    packed, ids = sync.pack(twin.state, twin.slots)
    assert set(range(3000, 3020)) <= set(ids.tolist())
    return jax.device_put(packed)


TABLES = {
    "J256": lambda: make_cluster_state(max_jobs=256, total_nodes=NODES,
                                       n_queued=14, n_running=2, seed=0),
    "recycled1024": _recycled_table,
}

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
    state = TABLES[table]()
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
    d = whatif.decide_legacy_vmap(TABLES[table](),
                                  jnp.asarray(PAPER_POOL, jnp.int32))
    view = assert_view_is_fields(d)
    assert view.cost_terms == {} and view.cost_ci is None
    assert np.asarray(d.words).shape == (d.run_mask.shape[0] + 1 + 3,)


@pytest.mark.parametrize("producer", ["decide", "decide_fan"])
def test_a_deadlocked_fork_crosses_as_inf(producer):
    state = TABLES["J256"]()
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
    d = REF.decide(TABLES["J256"](), POOL)
    mask = np.asarray(d.run_mask).copy()
    mask[np.flatnonzero(mask)[0]] = False
    altered = d._replace(run_mask=jnp.asarray(mask))
    np.testing.assert_array_equal(
        altered.unpack(np.asarray(altered.words)).run_mask, mask)
    assert d._replace(fan_size=4).words is d.words
