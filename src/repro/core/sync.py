"""Synchronization stage (§3.2) — keep the mirror consistent with the
physical scheduler.

The mirror is host-resident: a ``SimState`` whose leaves are numpy
arrays (``state.empty_state(..., xp=np)``) with the device state's
dtypes and shapes.  Every handler here is a plain numpy update that
returns a new state and never mutates its input; the twin puts the
mirror on the device in one upload per decision (``core/twin.py``), so
ingesting an event costs no device dispatch and no device read.  The
float32 arithmetic is the device updates' own (``core/state.py``'s
``add_job``/``start_job``/... stay the jit-safe reference), value for
value (tests/test_sync.py).

Event handling mirrors the paper's block ④:
  * RUNJOB  -> insert predicted end event (start + user estimate) and
               exit immediately (run events imply no new scheduling
               opportunity);
  * JOBOBIT -> pull back / push forward the predicted end to the actual
               completion time (④A) and trigger a scheduling cycle;
  * QUEUEJOB-> add the job to the wait queue and trigger a cycle;
  * NODEFAIL/NODEUP -> resize capacity, requeue victims, trigger a
               cycle (beyond paper: fault tolerance / elasticity).

``resync_free_nodes`` reproduces the paper's "synchronize node
availability using command-line tools": the mirror's free-node count is
overwritten from the authoritative source (pbsnodes equivalent) rather
than trusted from event replay — this makes the twin self-healing if an
event was dropped.  ``resync_jobs`` is the job-table analogue (qstat
equivalent): the whole mirror job table is reconciled from an
authoritative probe, healing drops that per-event logic can never see
(a lost QUEUEJOB leaves the twin unaware the job exists at all).

Hardened ingestion (DESIGN.md §12): ``apply_event(..., idempotent=
True)`` guards every handler on the job's CURRENT mirror state, so
duplicate and out-of-order deliveries (which the bus-level
``SeqTracker`` classifies but cannot repair) degrade to monotone
fill-ins instead of corrupting the free-node accounting — a RUNJOB
landing after its JOBOBIT only backfills ``start_t``; a JOBOBIT whose
RUNJOB never arrived marks the job DONE without freeing nodes it never
took.  As long as each job's own lifecycle order is preserved, any
cross-job interleaving of deliveries yields the identical final mirror
(the hypothesis property in tests/test_resilience.py).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core.events import Event, EventKind
from repro.core.state import (DONE, INVALID, QUEUED, RUNNING, TIME_NONE,
                              JobTable, SimState)


def _set(jobs: JobTable, job_id: int, **values) -> JobTable:
    """``jobs`` with slot ``job_id`` of each named column set: copies of
    the columns it writes, so the input table is never mutated."""
    cols = {}
    for name, v in values.items():
        col = np.array(getattr(jobs, name))
        col[job_id] = v
        cols[name] = col
    return jobs._replace(**cols)


def _later(now, t: np.float32) -> np.float32:
    return np.float32(np.maximum(now, t))


def _queue(state: SimState, ev: Event) -> SimState:
    t = np.float32(ev.time)
    jobs = _set(state.jobs, ev.job_id, submit_t=t,
                nodes=np.int32(int(ev.payload["nodes"])),
                est_runtime=np.float32(ev.payload["est_runtime"]),
                start_t=TIME_NONE, end_t=TIME_NONE, state=QUEUED)
    return state._replace(jobs=jobs, now=_later(state.now, t))


def _start(state: SimState, job_id: int, t: np.float32) -> SimState:
    """Predicted end = start + user estimate (§3.2), in float32."""
    jobs = state.jobs
    jobs = _set(jobs, job_id, start_t=t,
                end_t=t + np.float32(jobs.est_runtime[job_id]),
                state=RUNNING)
    return state._replace(
        jobs=jobs,
        free_nodes=np.int32(state.free_nodes - jobs.nodes[job_id]),
        now=_later(state.now, t))


def _end(state: SimState, job_id: int, t: np.float32) -> SimState:
    """④A: the actual end replaces the predicted one, early or late."""
    jobs = _set(state.jobs, job_id, end_t=t, state=DONE)
    return state._replace(
        jobs=jobs,
        free_nodes=np.int32(state.free_nodes + jobs.nodes[job_id]),
        now=_later(state.now, t))


def _resize(state: SimState, delta: int) -> SimState:
    return state._replace(
        total_nodes=np.int32(state.total_nodes + delta),
        free_nodes=np.int32(state.free_nodes + delta))


def _requeue(state: SimState, job_id: int, t: np.float32) -> SimState:
    """A node failure kills a running job: its nodes come back and it
    returns to the queue; a job in any other state keeps it."""
    jobs = state.jobs
    was_running = int(jobs.state[job_id]) == RUNNING
    freed = int(jobs.nodes[job_id]) if was_running else 0
    jobs = _set(jobs, job_id, start_t=TIME_NONE, end_t=TIME_NONE,
                state=QUEUED if was_running else jobs.state[job_id])
    return state._replace(
        jobs=jobs, free_nodes=np.int32(state.free_nodes + freed),
        now=_later(state.now, t))


def apply_event(state: SimState, ev: Event,
                idempotent: bool = False) -> Tuple[SimState, bool]:
    """Returns (new mirror state, needs_decision_cycle)."""
    if idempotent and ev.kind in (EventKind.QUEUEJOB, EventKind.RUNJOB,
                                  EventKind.JOBOBIT):
        return _apply_job_event_idempotent(state, ev)
    t = np.float32(ev.time)
    if ev.kind == EventKind.QUEUEJOB:
        return _queue(state, ev), True

    if ev.kind == EventKind.RUNJOB:
        # Predicted end event enters the virtual horizon; no cycle (§3.2).
        return _start(state, ev.job_id, t), False

    if ev.kind == EventKind.JOBOBIT:
        return _end(state, ev.job_id, t), True

    if ev.kind == EventKind.NODEFAIL:
        state = _resize(state, -int(ev.payload["nodes"]))
        victim = int(ev.payload.get("victim_job", -1))
        if victim >= 0:
            state = _requeue(state, victim, t)
        return state._replace(now=_later(state.now, t)), True

    if ev.kind == EventKind.NODEUP:
        state = _resize(state, int(ev.payload["nodes"]))
        return state._replace(now=_later(state.now, t)), True

    raise ValueError(f"unknown event kind: {ev.kind}")


def _apply_job_event_idempotent(state: SimState,
                                ev: Event) -> Tuple[SimState, bool]:
    """State-guarded job-event handlers: each transition fires only from
    the lifecycle state it is valid from, so re-delivery is a no-op and
    a late straggler can only FILL IN what it knows (never re-run a
    resource effect)."""
    cur = int(state.jobs.state[ev.job_id])
    t = np.float32(ev.time)

    if ev.kind == EventKind.QUEUEJOB:
        if cur != INVALID:          # already known (duplicate / late)
            return state, False
        return _queue(state, ev), True

    if ev.kind == EventKind.RUNJOB:
        if cur == QUEUED:           # the one valid transition
            return _start(state, ev.job_id, t), False
        if cur == DONE:             # arrived after its JOBOBIT: backfill
            # start_t only — no resource effect
            return state._replace(
                jobs=_set(state.jobs, ev.job_id, start_t=t)), False
        return state, False         # RUNNING duplicate / unknown job

    # EventKind.JOBOBIT
    if cur == RUNNING:              # the one valid transition
        return _end(state, ev.job_id, t), True
    if cur == QUEUED:
        # RUNJOB never arrived: the job is over, but this mirror never
        # charged its nodes — mark DONE without freeing anything.
        return state._replace(
            jobs=_set(state.jobs, ev.job_id, end_t=t, state=DONE),
            now=_later(state.now, t)), True
    return state, False              # DONE duplicate / unknown job


def resync_free_nodes(state: SimState, authoritative_free: int) -> SimState:
    """Overwrite mirror free-node count from the physical system."""
    return state._replace(free_nodes=np.int32(authoritative_free))


def resync_jobs(state: SimState, view: dict) -> SimState:
    """Full job-table reconcile from an authoritative probe (the qstat
    analogue of ``resync_free_nodes``) — the heal of last resort when
    the stream has LOST events the idempotent handlers cannot repair
    (above all a dropped QUEUEJOB: the twin otherwise never learns the
    job exists and can never feed it to ``qrun``).

    ``view`` is ``ClusterEmulator.jobs_view()`` (or a real qstat
    adapter): per-slot ``submit_t``/``nodes``/``est_runtime``/
    ``start_t``/``end_t``/``state`` plus ``total_nodes`` and
    ``free_nodes`` scalars.  The §3.2 estimate asymmetry is preserved —
    running jobs get predicted ends ``start + estimate`` exactly as a
    replayed RUNJOB would; only DONE jobs carry their actual end."""
    submit = np.asarray(view["submit_t"], np.float32)
    nodes = np.asarray(view["nodes"], np.int32)
    est = np.asarray(view["est_runtime"], np.float32)
    st = np.asarray(view["state"], np.int32)
    start = np.asarray(view["start_t"], np.float32)
    end = np.asarray(view["end_t"], np.float32)
    known = st != INVALID
    none = np.float32(TIME_NONE)
    jobs = JobTable(
        submit_t=np.where(known, submit, none),
        nodes=np.where(known, nodes, np.int32(0)),
        est_runtime=np.where(known, est, np.float32(0.0)),
        start_t=np.where(known & (st != QUEUED), start, none),
        end_t=np.where(st == RUNNING, start + est,
                       np.where(st == DONE, end, none)),
        state=st,
    )
    return state._replace(
        jobs=jobs,
        free_nodes=np.int32(int(view["free_nodes"])),
        total_nodes=np.int32(int(view["total_nodes"])),
    )
