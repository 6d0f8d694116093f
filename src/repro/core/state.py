"""Struct-of-array job/cluster state — the twin's mirror and every fork's.

Fixed-capacity arrays (``max_jobs`` slots) so every simulation has a
static shape.  The same structures are used by (a) the twin's mirror of
the physical system (numpy leaves on the host, uploaded once per
decision as one ``PackedState`` buffer; a window of slots recycled as
jobs end, ``sync.JobSlots``),
(b) each what-if simulation fork, and (c) the cluster emulator's
ground-truth state (which additionally knows true runtimes; there slot
``i`` is job ``i`` for the lifetime of a trace).  Slot order is
submission order wherever a pass reads the table.
"""
from __future__ import annotations

from typing import NamedTuple, Union

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


class PackedState(NamedTuple):
    """A ``SimState`` as one flat int32 buffer of ``6·J + 3`` words, so
    the twin's mirror reaches the device in one transfer (``sync.pack``
    writes it on the host, ``as_state`` reads it inside the decide's
    program).  Layout: the six job columns in ``JobTable`` field order,
    ``J`` words each, then ``free_nodes``, ``total_nodes`` and ``now``.
    Float32 values are stored by their bits (``packed_fields``)."""

    words: jax.Array        # i32 (6·J + 3,)


#: The dtype of each ``SimState`` field as a ``PackedState`` holds it:
#: each job column's, then the three scalars'.
_PACKED_JOBS = JobTable(np.float32, np.int32, np.float32, np.float32,
                        np.float32, np.int32)
_PACKED_SCALARS = (("free_nodes", np.int32), ("total_nodes", np.int32),
                   ("now", np.float32))


def packed_fields(words) -> SimState:
    """The ``SimState`` fields of a ``PackedState`` buffer of ``6·J +
    3`` int32 words, each a static slice of ``words`` read as its dtype
    (a bitcast): on a numpy buffer views to write the fields through, on
    a device one the unpacked state."""
    J = (words.shape[0] - 3) // 6

    def field(start, size, dtype):
        return words[start:start + size].view(dtype)

    jobs = JobTable(*(field(i * J, J, dt)
                      for i, dt in enumerate(_PACKED_JOBS)))
    scalars = {name: field(6 * J + i, 1, dt).reshape(())
               for i, (name, dt) in enumerate(_PACKED_SCALARS)}
    return SimState(jobs=jobs, **scalars)


def as_state(x: Union[SimState, PackedState]) -> SimState:
    """``x`` as a ``SimState``: a ``SimState`` unchanged, a
    ``PackedState`` unpacked (``packed_fields``).  Every decision program
    that takes the twin's state calls it first."""
    return packed_fields(x.words) if isinstance(x, PackedState) else x


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
