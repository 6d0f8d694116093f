"""Synchronization stage (§3.2) — keep the mirror consistent with the
physical scheduler.

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

import jax.numpy as jnp

from repro.core.events import Event, EventKind
from repro.core.state import (DONE, INVALID, QUEUED, RUNNING, TIME_NONE,
                              JobTable, SimState, add_job, end_job,
                              requeue_job, resize_cluster, start_job)
from repro.core.telemetry import fetch


def apply_event(state: SimState, ev: Event,
                idempotent: bool = False) -> Tuple[SimState, bool]:
    """Returns (new mirror state, needs_decision_cycle)."""
    if idempotent and ev.kind in (EventKind.QUEUEJOB, EventKind.RUNJOB,
                                  EventKind.JOBOBIT):
        return _apply_job_event_idempotent(state, ev)
    if ev.kind == EventKind.QUEUEJOB:
        state = add_job(
            state, ev.job_id,
            submit_t=jnp.float32(ev.time),
            nodes=jnp.int32(int(ev.payload["nodes"])),
            est_runtime=jnp.float32(ev.payload["est_runtime"]),
        )
        return state, True

    if ev.kind == EventKind.RUNJOB:
        # Predicted end event enters the virtual horizon; no cycle (§3.2).
        state = start_job(state, ev.job_id, jnp.float32(ev.time))
        return state, False

    if ev.kind == EventKind.JOBOBIT:
        # ④A pull-back (early finish) or push-forward (cleanup delay):
        # the predicted end is replaced with the actual one.
        state = end_job(state, ev.job_id, jnp.float32(ev.time))
        return state, True

    if ev.kind == EventKind.NODEFAIL:
        state = resize_cluster(state, -jnp.int32(int(ev.payload["nodes"])))
        victim = int(ev.payload.get("victim_job", -1))
        if victim >= 0:
            state = requeue_job(state, victim, jnp.float32(ev.time))
        state = state._replace(now=jnp.maximum(state.now, jnp.float32(ev.time)))
        return state, True

    if ev.kind == EventKind.NODEUP:
        state = resize_cluster(state, jnp.int32(int(ev.payload["nodes"])))
        state = state._replace(now=jnp.maximum(state.now, jnp.float32(ev.time)))
        return state, True

    raise ValueError(f"unknown event kind: {ev.kind}")


def _apply_job_event_idempotent(state: SimState,
                                ev: Event) -> Tuple[SimState, bool]:
    """State-guarded job-event handlers: each transition fires only from
    the lifecycle state it is valid from, so re-delivery is a no-op and
    a late straggler can only FILL IN what it knows (never re-run a
    resource effect).  One host-side state read per event — the same
    host-driven granularity as the normal path."""
    cur = int(fetch(state.jobs.state[ev.job_id]))

    if ev.kind == EventKind.QUEUEJOB:
        if cur != INVALID:          # already known (duplicate / late)
            return state, False
        state = add_job(
            state, ev.job_id,
            submit_t=jnp.float32(ev.time),
            nodes=jnp.int32(int(ev.payload["nodes"])),
            est_runtime=jnp.float32(ev.payload["est_runtime"]),
        )
        return state, True

    if ev.kind == EventKind.RUNJOB:
        if cur == QUEUED:           # the one valid transition
            return start_job(state, ev.job_id, jnp.float32(ev.time)), False
        if cur == DONE:             # arrived after its JOBOBIT: backfill
            jobs = state.jobs      # start_t only — no resource effect
            jobs = jobs._replace(
                start_t=jobs.start_t.at[ev.job_id].set(
                    jnp.float32(ev.time)))
            return state._replace(jobs=jobs), False
        return state, False         # RUNNING duplicate / unknown job

    # EventKind.JOBOBIT
    if cur == RUNNING:              # the one valid transition
        return end_job(state, ev.job_id, jnp.float32(ev.time)), True
    if cur == QUEUED:
        # RUNJOB never arrived: the job is over, but this mirror never
        # charged its nodes — mark DONE without freeing anything.
        jobs = state.jobs
        jobs = jobs._replace(
            end_t=jobs.end_t.at[ev.job_id].set(jnp.float32(ev.time)),
            state=jobs.state.at[ev.job_id].set(DONE),
        )
        return state._replace(
            jobs=jobs,
            now=jnp.maximum(state.now, jnp.float32(ev.time))), True
    return state, False              # DONE duplicate / unknown job


def resync_free_nodes(state: SimState, authoritative_free: int) -> SimState:
    """Overwrite mirror free-node count from the physical system."""
    return state._replace(free_nodes=jnp.int32(authoritative_free))


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
    submit = jnp.asarray(view["submit_t"], jnp.float32)
    nodes = jnp.asarray(view["nodes"], jnp.int32)
    est = jnp.asarray(view["est_runtime"], jnp.float32)
    st = jnp.asarray(view["state"], jnp.int32)
    start = jnp.asarray(view["start_t"], jnp.float32)
    end = jnp.asarray(view["end_t"], jnp.float32)
    pred_end = jnp.where(st == RUNNING, start + est, end)
    none = jnp.float32(TIME_NONE)
    jobs = JobTable(
        submit_t=jnp.where(st != INVALID, submit, none),
        nodes=jnp.where(st != INVALID, nodes, 0),
        est_runtime=jnp.where(st != INVALID, est, 0.0),
        start_t=jnp.where(st == QUEUED, none,
                          jnp.where(st != INVALID, start, none)),
        end_t=jnp.where((st == RUNNING) | (st == DONE), pred_end, none),
        state=st,
    )
    return state._replace(
        jobs=jobs,
        free_nodes=jnp.int32(int(view["free_nodes"])),
        total_nodes=jnp.int32(int(view["total_nodes"])),
    )
