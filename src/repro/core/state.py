"""Struct-of-array job/cluster state — the twin's mirror and every fork's.

Fixed-capacity arrays (``max_jobs`` slots) so every simulation has a
static shape: slot ``i`` is job ``i`` for the lifetime of a trace.  The
same structures are used by (a) the twin's mirror of the physical system
(numpy leaves on the host, uploaded once per decision), (b) each what-if
simulation fork, and (c) the cluster emulator's ground-truth state
(which additionally knows true runtimes).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Job lifecycle states.
INVALID = 0   # empty slot
QUEUED = 1
RUNNING = 2
DONE = 3

# Sentinel for "not yet" times.
TIME_NONE = -1.0
INF = jnp.inf


class JobTable(NamedTuple):
    """All arrays have shape (max_jobs,).

    ``est_runtime`` is the user-provided walltime estimate — the only
    runtime the twin is allowed to see (§3.2: user estimates are
    commonly inaccurate; the sync stage corrects end events as they
    actually happen).
    """

    submit_t: jax.Array    # f32 — submission time
    nodes: jax.Array       # i32 — node request
    est_runtime: jax.Array # f32 — user walltime estimate
    start_t: jax.Array     # f32 — TIME_NONE until started
    end_t: jax.Array       # f32 — predicted (running) or actual (done) end
    state: jax.Array       # i32 — INVALID/QUEUED/RUNNING/DONE

    @property
    def capacity(self) -> int:
        return self.submit_t.shape[-1]


class SimState(NamedTuple):
    """One simulation instance (or the twin's live mirror)."""

    jobs: JobTable
    free_nodes: jax.Array   # i32 scalar
    total_nodes: jax.Array  # i32 scalar (changes on NODEFAIL/NODEUP)
    now: jax.Array          # f32 scalar


def empty_jobs(max_jobs: int, xp=jnp) -> JobTable:
    def times():
        return xp.full((max_jobs,), TIME_NONE, dtype=np.float32)

    return JobTable(
        submit_t=times(),
        nodes=xp.zeros((max_jobs,), dtype=np.int32),
        est_runtime=xp.zeros((max_jobs,), dtype=np.float32),
        start_t=times(),
        end_t=times(),
        state=xp.zeros((max_jobs,), dtype=np.int32),
    )


def empty_state(max_jobs: int, total_nodes: int, xp=jnp) -> SimState:
    """An empty cluster: on the device, or with ``xp=np`` on the host
    (the twin's mirror, ``core/sync.py``) with the same dtypes and
    shapes."""
    return SimState(
        jobs=empty_jobs(max_jobs, xp),
        free_nodes=xp.asarray(total_nodes, dtype=np.int32),
        total_nodes=xp.asarray(total_nodes, dtype=np.int32),
        now=xp.asarray(0.0, dtype=np.float32),
    )


# --- functional updates (jit-safe) -------------------------------------

def add_job(state: SimState, job_id, submit_t, nodes, est_runtime) -> SimState:
    """QUEUEJOB: place a job in its slot."""
    jobs = state.jobs
    jobs = jobs._replace(
        submit_t=jobs.submit_t.at[job_id].set(submit_t),
        nodes=jobs.nodes.at[job_id].set(nodes),
        est_runtime=jobs.est_runtime.at[job_id].set(est_runtime),
        start_t=jobs.start_t.at[job_id].set(TIME_NONE),
        end_t=jobs.end_t.at[job_id].set(TIME_NONE),
        state=jobs.state.at[job_id].set(QUEUED),
    )
    return state._replace(jobs=jobs, now=jnp.maximum(state.now, submit_t))


def start_job(state: SimState, job_id, t) -> SimState:
    """RUNJOB: mark running; predicted end = t + user estimate (§3.2)."""
    jobs = state.jobs
    predicted_end = t + jobs.est_runtime[job_id]
    jobs = jobs._replace(
        start_t=jobs.start_t.at[job_id].set(t),
        end_t=jobs.end_t.at[job_id].set(predicted_end),
        state=jobs.state.at[job_id].set(RUNNING),
    )
    return state._replace(
        jobs=jobs,
        free_nodes=state.free_nodes - jobs.nodes[job_id],
        now=jnp.maximum(state.now, t),
    )


def end_job(state: SimState, job_id, t) -> SimState:
    """JOBOBIT: actual completion — §3.2 pull-back / push-forward.

    The predicted end event (at start + estimate) is replaced by the
    actual end time ``t``, whether early (common: users overestimate) or
    late (scheduler cleanup delay).
    """
    jobs = state.jobs
    jobs = jobs._replace(
        end_t=jobs.end_t.at[job_id].set(t),
        state=jobs.state.at[job_id].set(DONE),
    )
    return state._replace(
        jobs=jobs,
        free_nodes=state.free_nodes + jobs.nodes[job_id],
        now=jnp.maximum(state.now, t),
    )


def requeue_job(state: SimState, job_id, t) -> SimState:
    """Node failure kills a running job: release nodes, back to queue."""
    jobs = state.jobs
    was_running = jobs.state[job_id] == RUNNING
    freed = jnp.where(was_running, jobs.nodes[job_id], 0)
    jobs = jobs._replace(
        start_t=jobs.start_t.at[job_id].set(TIME_NONE),
        end_t=jobs.end_t.at[job_id].set(TIME_NONE),
        state=jobs.state.at[job_id].set(
            jnp.where(was_running, QUEUED, jobs.state[job_id])),
    )
    return state._replace(
        jobs=jobs, free_nodes=state.free_nodes + freed,
        now=jnp.maximum(state.now, t))


def resize_cluster(state: SimState, delta_nodes) -> SimState:
    """NODEFAIL (negative delta) / NODEUP (positive delta)."""
    return state._replace(
        total_nodes=state.total_nodes + delta_nodes,
        free_nodes=state.free_nodes + delta_nodes,
    )


def queued_mask(jobs: JobTable) -> jax.Array:
    return jobs.state == QUEUED


def running_mask(jobs: JobTable) -> jax.Array:
    return jobs.state == RUNNING


def validate_invariants(state: SimState) -> dict:
    """Host-side invariant check used by tests and the emulator.

    Returns a dict of boolean invariants; all must be True.
    """
    jobs = state.jobs
    used = jnp.sum(jnp.where(running_mask(jobs), jobs.nodes, 0))
    started = jobs.start_t >= 0
    valid = jobs.state != INVALID
    return {
        "free_plus_used_is_total": bool(
            (state.free_nodes + used) == state.total_nodes),
        "free_nonnegative": bool(state.free_nodes >= 0),
        "no_start_before_submit": bool(jnp.all(
            jnp.where(valid & started, jobs.start_t >= jobs.submit_t, True))),
        "running_have_start": bool(jnp.all(
            jnp.where(running_mask(jobs), started, True))),
        "done_have_end": bool(jnp.all(
            jnp.where(jobs.state == DONE, jobs.end_t >= 0, True))),
    }
