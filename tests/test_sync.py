"""The twin's host mirror (``core/sync.py``) against the device updates
of ``core/state.py``: the same event streams give the same mirror, bit
for bit, dtypes included, after every step."""
import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.emulator import ClusterEmulator, FailureSpec
from repro.cluster.workload import paper_synthetic_trace
from repro.core import sync
from repro.core.events import Event, EventBus, EventKind
from repro.core.policies import FCFS
from repro.core.state import (DONE, INVALID, QUEUED, RUNNING, TIME_NONE,
                              JobTable, add_job, empty_state, end_job,
                              requeue_job, resize_cluster, start_job)
from repro.core.twin import SchedTwin

from test_resilience import _delivery

NODES, MAX_JOBS = 32, 256


# -- the device reference: state.py's jit-safe updates, eagerly --------

def _device_apply(state, ev, idempotent):
    t = jnp.float32(ev.time)
    if idempotent and ev.kind in (EventKind.QUEUEJOB, EventKind.RUNJOB,
                                  EventKind.JOBOBIT):
        cur = int(state.jobs.state[ev.job_id])
        jobs = state.jobs
        if ev.kind == EventKind.QUEUEJOB:
            if cur != INVALID:
                return state, False
        elif ev.kind == EventKind.RUNJOB:
            if cur == DONE:
                return state._replace(jobs=jobs._replace(
                    start_t=jobs.start_t.at[ev.job_id].set(t))), False
            if cur != QUEUED:
                return state, False
        elif cur == QUEUED:
            jobs = jobs._replace(end_t=jobs.end_t.at[ev.job_id].set(t),
                                 state=jobs.state.at[ev.job_id].set(DONE))
            return state._replace(jobs=jobs,
                                  now=jnp.maximum(state.now, t)), True
        elif cur != RUNNING:
            return state, False
    if ev.kind == EventKind.QUEUEJOB:
        return add_job(state, ev.job_id, t,
                       jnp.int32(int(ev.payload["nodes"])),
                       jnp.float32(ev.payload["est_runtime"])), True
    if ev.kind == EventKind.RUNJOB:
        return start_job(state, ev.job_id, t), False
    if ev.kind == EventKind.JOBOBIT:
        return end_job(state, ev.job_id, t), True
    sign = -1 if ev.kind == EventKind.NODEFAIL else 1
    state = resize_cluster(state, sign * jnp.int32(int(ev.payload["nodes"])))
    victim = int(ev.payload.get("victim_job", -1))
    if victim >= 0:
        state = requeue_job(state, victim, t)
    return state._replace(now=jnp.maximum(state.now, t)), True


def _device_resync_jobs(state, view):
    st = jnp.asarray(view["state"], jnp.int32)
    start = jnp.asarray(view["start_t"], jnp.float32)
    est = jnp.asarray(view["est_runtime"], jnp.float32)
    none = jnp.float32(TIME_NONE)
    known = st != INVALID
    jobs = JobTable(
        submit_t=jnp.where(known, jnp.asarray(view["submit_t"],
                                              jnp.float32), none),
        nodes=jnp.where(known, jnp.asarray(view["nodes"], jnp.int32), 0),
        est_runtime=jnp.where(known, est, 0.0),
        start_t=jnp.where(known & (st != QUEUED), start, none),
        end_t=jnp.where(st == RUNNING, start + est,
                        jnp.where(st == DONE,
                                  jnp.asarray(view["end_t"], jnp.float32),
                                  none)),
        state=st)
    return state._replace(jobs=jobs,
                          free_nodes=jnp.int32(view["free_nodes"]),
                          total_nodes=jnp.int32(view["total_nodes"]))


# -- the streams --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _paper_run():
    """A paper-family trace on the paper's 32 nodes under FCFS, with
    one node failure that kills a running job: the whole event log,
    and the scheduler's job table after every event."""
    bus = EventBus()
    em = ClusterEmulator(paper_synthetic_trace(seed=3), NODES, bus=bus,
                         max_jobs=MAX_JOBS,
                         failures=[FailureSpec(400.0, 8, 300.0)])
    views = []
    bus.subscribe(lambda ev: views.append(em.jobs_view()))
    em.run(policy_id=FCFS)
    return list(bus.replay()), views


def _paper_log(idempotent):
    log, _ = _paper_run()
    kinds = {ev.kind for ev in log}
    assert {EventKind.NODEFAIL, EventKind.NODEUP} <= kinds
    assert any(ev.payload.get("victim_job", -1) >= 0 for ev in log
               if ev.kind == EventKind.NODEFAIL)
    return [("event", ev, idempotent) for ev in log]


def _redelivery():
    rng = np.random.default_rng(0)
    tags = np.array([j for j in range(4) for _ in range(3)])
    ops = []
    for _ in range(10):
        order = rng.permutation(tags).tolist()
        dup_at = rng.integers(0, len(tags),
                              size=int(rng.integers(0, 7))).tolist()
        _, shuffled = _delivery(order, dup_at)
        ops.append(("empty",))
        ops += [("event", ev, True) for ev in shuffled]
    return ops


def _node_events():
    def q(j, t, nodes, est):
        return Event(EventKind.QUEUEJOB, t, j,
                     {"nodes": float(nodes), "est_runtime": est})

    def fail(t, nodes, victim):
        return Event(EventKind.NODEFAIL, t, -1,
                     {"nodes": float(nodes), "victim_job": float(victim)})

    evs = [q(0, 0.1, 12, 100.3), q(1, 0.7, 10, 33.3), q(2, 1.3, 6, 7.77),
           Event(EventKind.RUNJOB, 2.2, 0), Event(EventKind.RUNJOB, 2.2, 1),
           fail(3.1, 8, 0),          # kills running job 0
           fail(3.1, 0, 1),          # a second victim of the same failure
           fail(4.9, 4, 2),          # names a queued job: it stays queued
           fail(5.0, 2, -1),         # no victim
           Event(EventKind.NODEUP, 8.5, -1, {"nodes": 8.0}),
           Event(EventKind.RUNJOB, 9.25, 0),
           Event(EventKind.JOBOBIT, 60.01, 0),
           Event(EventKind.NODEUP, 61.0, -1, {"nodes": 6.0})]
    ops = [("event", ev, True) for ev in evs]
    return ops[:5] + [("free", 9)] + ops[5:] + [("free", 32)]


def _resyncs():
    _, views = _paper_run()
    assert {QUEUED, RUNNING, DONE} <= {int(s) for v in views
                                       for s in v["state"]}
    return [("jobs", v) for v in views[::15]]


STREAMS = {
    "paper32_log_ingest": lambda: _paper_log(idempotent=True),
    "paper32_log_replay": lambda: _paper_log(idempotent=False),
    "redelivery": _redelivery,
    "node_events": _node_events,
    "resync_jobs": _resyncs,
}


def _assert_same(host, device, where):
    for (path, h), d in zip(jax.tree_util.tree_flatten_with_path(host)[0],
                            jax.tree.leaves(device)):
        name = f"{where}: {jax.tree_util.keystr(path)}"
        assert isinstance(h, (np.ndarray, np.generic)), name
        d = np.asarray(d)
        assert (h.dtype, h.shape) == (d.dtype, d.shape), name
        np.testing.assert_array_equal(h, d, err_msg=name)
        assert np.asarray(h).tobytes() == d.tobytes(), name   # ±0 too


@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_host_mirror_matches_device_updates(stream):
    host = device = None
    for i, op in enumerate([("empty",)] + STREAMS[stream]()):
        if op[0] == "empty":
            host = empty_state(MAX_JOBS, NODES, xp=np)
            device = empty_state(MAX_JOBS, NODES)
            continue
        given, before = host, copy.deepcopy(host)
        if op[0] == "event":
            host, h_cycle = sync.apply_event(host, op[1], idempotent=op[2])
            device, d_cycle = _device_apply(device, op[1], op[2])
            assert h_cycle == d_cycle, (i, op)
        elif op[0] == "free":
            host = sync.resync_free_nodes(host, op[1])
            device = device._replace(free_nodes=jnp.int32(op[1]))
        else:
            host = sync.resync_jobs(host, op[1])
            device = _device_resync_jobs(device, op[1])
        _assert_same(host, device, f"step {i} {op[:2]}")
        # a handler returns a new state: the one it was given is intact
        jax.tree.map(np.testing.assert_array_equal, given, before)


def test_a_fresh_twins_mirror_is_on_the_host():
    twin = SchedTwin(bus=EventBus(), qrun=lambda jobs, t: None,
                     total_nodes=NODES, max_jobs=MAX_JOBS)
    _assert_same(twin.state, empty_state(MAX_JOBS, NODES), "fresh twin")
    for leaf in jax.tree.leaves(twin.state):
        assert not isinstance(leaf, jax.Array)
