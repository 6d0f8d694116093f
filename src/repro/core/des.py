"""Discrete-event simulation engine (§3.3).

Two drain implementations share the same event semantics
(DESIGN.md §3):

* ``simulate_to_drain`` — the scalar oracle: one what-if fork advanced
  event-to-event via ``lax.while_loop``.  Kept as the semantic
  reference (tests assert the batched drain against it) and as the
  legacy ``jax.vmap`` path the benchmarks compare against.

* ``simulate_to_drain_batched`` — the hot path: ALL k forks carried as
  a leading batch axis on ``SimState`` and advanced in lock-step by ONE
  ``lax.while_loop`` with per-fork done/dead masks.  The scheduling
  pass runs on the whole batch at once through a pluggable backend
  (``repro.core.engine``): priority keys are computed and argsorted
  once per event for the entire batch, and the sequential
  greedy/backfill part executes either as a vmapped reference pass or
  as the Pallas kernel with the fork axis on the grid.

Starting from the twin's synchronized snapshot (running jobs with
predicted ends + queued jobs), each fork applies one policy until the
queue drains.  Future arrivals are *not* simulated — per §3.2, submit
events cannot be predicted; the event horizon contains only predicted
job-end events.  The loop bound is ``max_jobs + 1``: every iteration
with a non-empty queue either starts jobs or retires at least one
running job.

* ``simulate_replay_batched`` — the drain generalized into an
  event-driven **trace replay** (DESIGN.md §6): each fork additionally
  carries a pending-arrival cursor into a per-fork arrival timeline and
  a ground-truth runtime array.  Every step advances one fork-local
  event — ``min(next arrival, next actual completion)`` — injecting
  arrivals and retiring completions at their *true* end times while the
  scheduling pass keeps reasoning over *predicted* ends
  (start + estimate): the §3.2 pull-back/push-forward asymmetry that
  previously only the host-side ``cluster/emulator.py`` loop modeled.
  The three-stage keys/pass/advance decomposition and both pass
  backends are reused unchanged, so an S-scenario × P-policy baseline
  grid is ONE device computation (``engine.replay_grid``) instead of
  S·P Python event loops, bit-identical to the host emulator's static
  mode (tests/test_replay.py).

The batched loops name their stages with ``jax.named_scope`` —
``keys`` (priority keys and argsort), ``pass`` (the scheduling pass and
its starts) and ``advance`` (the next event and the clock) — and the
engine names its selection ``select``, so a profile's op metadata
attributes device time to each stage.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.backfill import schedule_pass
from repro.core.state import DONE, QUEUED, RUNNING, SimState


class DrainResult(NamedTuple):
    state: SimState          # all previously-queued jobs DONE (or deadlocked)
    first_started: jax.Array # bool (max_jobs,) — jobs started at t=now(0):
                             # the twin's actionable decision (§3.4, 6A)
    iters: jax.Array         # i32 — events processed
    deadlocked: jax.Array    # bool — a queued job can never fit
    pass_invocations: jax.Array  # i32 — scheduling passes executed; the
                                 # batched drain runs one per lock-step
                                 # iteration (same count for every fork)


def simulate_to_drain(state: SimState, policy_id) -> DrainResult:
    max_jobs = state.jobs.capacity
    max_iters = max_jobs + 1

    def cond(carry):
        st, first, it, dead = carry
        return (it < max_iters) & (~dead) & jnp.any(st.jobs.state == QUEUED)

    def body(carry):
        st, first, it, dead = carry
        res = schedule_pass(st, policy_id)
        st = res.state
        # capture the decision: jobs started at the snapshot instant
        first = jnp.where(it == 0, res.started, first)

        jobs = st.jobs
        running = jobs.state == RUNNING
        has_queued = jnp.any(jobs.state == QUEUED)
        ends = jnp.where(running, jobs.end_t, jnp.inf)
        # stale predicted ends (a job "should" have finished before the
        # snapshot instant — user estimates are inaccurate, §3.2) are
        # processed AT the current time: virtual time never rewinds.
        t_next = jnp.maximum(jnp.min(ends), st.now)
        can_advance = has_queued & jnp.isfinite(t_next)
        # a queued job that can never run (req > total nodes) -> deadlock
        dead = dead | (has_queued & ~jnp.isfinite(t_next))

        ending = running & (jobs.end_t <= t_next) & can_advance
        freed = jnp.sum(jnp.where(ending, jobs.nodes, 0))
        jobs = jobs._replace(
            state=jnp.where(ending, DONE, jobs.state))
        st = st._replace(
            jobs=jobs,
            free_nodes=st.free_nodes + freed,
            now=jnp.where(can_advance, t_next, st.now),
        )
        return st, first, it + 1, dead

    init = (state,
            jnp.zeros((max_jobs,), dtype=bool),
            jnp.int32(0),
            jnp.asarray(False))
    st, first, it, dead = jax.lax.while_loop(cond, body, init)
    return DrainResult(state=st, first_started=first, iters=it,
                       deadlocked=dead, pass_invocations=it)


# ----------------------------------------------------------------------
# Batched drain — the engine's hot path.
# ----------------------------------------------------------------------

# A batched pass: (batched SimState, order (k, J) i32, rank limit — an
# i32 scalar or None for the full static bound) -> started (k, J) bool.
# Implementations live in repro/core/engine.py (the backend registry);
# des.py only defines the drain loop around them.
BatchedPassFn = Callable[[SimState, jax.Array, object], jax.Array]


def pass_rank_limit(states: SimState, fork_mask: jax.Array) -> jax.Array:
    """Dynamic pass bound (DESIGN.md §7): the batch-max queued count
    over live forks — an i32 scalar shared by the whole lock-step batch.

    Contract: every (k, J) order the engine produces is QUEUED-FIRST —
    fresh argsorts mask non-queued keys to +inf, and hoisted static
    orders are stable-partition-compacted per event
    (``engine.make_order_fn``) — so each fork's queued slots occupy
    ranks ``[0, n_queued)`` and every rank at or past the batch-max
    count cannot start anything (the pass skips non-QUEUED slots).
    Truncating the sequential rank loops there is therefore bit-exact.
    ``fork_mask`` excludes forks whose pass output is masked away
    anyway (done/dead/not-live), so a deadlocked fork's eternally-queued
    job cannot pin the bound at J.

    Under the fleet engine (DESIGN.md §9) the bound is SHARD-LOCAL:
    ``shard_map`` runs this over each device's chunk of the fork axis,
    so one shard's deep queue never widens another shard's pass.  The
    bound only changes how much work a pass performs, never what it
    computes, so results stay bit-identical to the unsharded batch —
    only ``pass_invocations``-style telemetry differs."""
    n_queued = jnp.sum(states.jobs.state == QUEUED, axis=1)      # (k,)
    return jnp.max(jnp.where(fork_mask, n_queued, 0)).astype(jnp.int32)


def broadcast_state(state: SimState, k: int) -> SimState:
    """Fan one snapshot out to k forks (a broadcast, not k copies —
    XLA materializes lazily; the paper's "share a common database")."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (k,) + jnp.shape(x)), state)


def apply_starts(st: SimState, started: jax.Array) -> SimState:
    """Apply a batched pass's start decisions (k, J): start at ``now``,
    predicted end = now + estimate (§3.2), nodes claimed.  The single
    copy of this parity-critical state evolution — shared by the drain
    and replay loops so they cannot drift."""
    jobs = st.jobs
    now_col = st.now[:, None]
    jobs = jobs._replace(
        start_t=jnp.where(started, now_col, jobs.start_t),
        end_t=jnp.where(started, now_col + jobs.est_runtime, jobs.end_t),
        state=jnp.where(started, RUNNING, jobs.state),
    )
    return st._replace(
        jobs=jobs,
        free_nodes=st.free_nodes
        - jnp.sum(jnp.where(started, jobs.nodes, 0), axis=1),
    )


def simulate_to_drain_batched(states: SimState, order_fn: Callable[[SimState], jax.Array],
                              pass_fn: BatchedPassFn,
                              dynamic_bounds: bool = True) -> DrainResult:
    """Drain all k forks of ``states`` (leading batch axis on every
    leaf) in lock-step with per-fork done/dead masks.

    ``order_fn`` maps the batched state to the (k, J) priority order —
    ONE batched key computation + argsort per event for the whole fork
    axis.  ``pass_fn`` runs the sequential greedy/backfill pass on the
    batch (reference vmap or the Pallas grid) up to a rank limit:
    ``dynamic_bounds`` truncates both rank loops at the batch-max
    queued rank (``pass_rank_limit`` — bit-exact; DESIGN.md §7), which
    also shrinks the drain tail where only a few forks remain active.

    Per-fork semantics are identical to ``simulate_to_drain``: a fork
    that drains (or deadlocks) freezes while the rest keep stepping, so
    the batched result is bit-for-bit the stack of k scalar drains
    (asserted by tests/test_engine.py).

    No pass-elision ``cond`` here: the loop condition already requires
    some fork to be active (~dead with a queued job), so "no live fork
    has a queued job" can never hold inside the body — elision lives in
    the replay loop, where completion-only stretches make it fire.
    """
    k = states.now.shape[0]
    max_jobs = states.jobs.capacity
    max_iters = max_jobs + 1

    def active_mask(st, dead):
        return (~dead) & jnp.any(st.jobs.state == QUEUED, axis=1)

    def cond(carry):
        st, first, it, dead, iters = carry
        return (it < max_iters) & jnp.any(active_mask(st, dead))

    def body(carry):
        st, first, it, dead, iters = carry
        active = active_mask(st, dead)                      # (k,)

        # ---- schedule pass on the whole batch ------------------------
        with jax.named_scope("keys"):
            order = order_fn(st)                            # (k, J)
        with jax.named_scope("pass"):
            limit = (pass_rank_limit(st, active)
                     if dynamic_bounds else None)
            started = pass_fn(st, order, limit) & active[:, None]
            st = apply_starts(st, started)
            first = jnp.where(it == 0, started, first)

        # ---- advance each fork to its next predicted completion ------
        with jax.named_scope("advance"):
            jobs = st.jobs
            running = jobs.state == RUNNING
            has_queued = jnp.any(jobs.state == QUEUED, axis=1)  # (k,)
            ends = jnp.where(running, jobs.end_t, jnp.inf)
            t_next = jnp.maximum(jnp.min(ends, axis=1), st.now)  # (k,)
            can_advance = active & has_queued & jnp.isfinite(t_next)
            dead = dead | (active & has_queued & ~jnp.isfinite(t_next))

            ending = (running & (jobs.end_t <= t_next[:, None])
                      & can_advance[:, None])
            freed = jnp.sum(jnp.where(ending, jobs.nodes, 0), axis=1)
            jobs = jobs._replace(state=jnp.where(ending, DONE, jobs.state))
            st = st._replace(
                jobs=jobs,
                free_nodes=st.free_nodes + freed,
                now=jnp.where(can_advance, t_next, st.now),
            )
        return st, first, it + 1, dead, iters + active.astype(jnp.int32)

    init = (states,
            jnp.zeros((k, max_jobs), dtype=bool),
            jnp.int32(0),
            jnp.zeros((k,), dtype=bool),
            jnp.zeros((k,), dtype=jnp.int32))
    st, first, it, dead, iters = jax.lax.while_loop(cond, body, init)
    return DrainResult(state=st, first_started=first, iters=iters,
                       deadlocked=dead,
                       pass_invocations=jnp.full((k,), it, dtype=jnp.int32))


# ----------------------------------------------------------------------
# Scenario-vectorized trace replay (DESIGN.md §6).
# ----------------------------------------------------------------------

class ReplayResult(NamedTuple):
    state: SimState          # final state: start_t/end_t are ACTUAL times
    events: jax.Array        # i32 (k,) — events processed per fork
    iters: jax.Array         # i32 scalar — lock-step iterations
    deadlocked: jax.Array    # bool (k,) — a queued job can never run
    pass_invocations: jax.Array  # i32 scalar — scheduling passes actually
                                 # executed (< iters when elision fires)


def simulate_replay_batched(states: SimState, arrival_t: jax.Array,
                            true_rt: jax.Array,
                            order_fn: Callable[[SimState], jax.Array],
                            pass_fn: BatchedPassFn,
                            dynamic_bounds: bool = True,
                            elide_empty: bool = True) -> ReplayResult:
    """Replay k trace forks event-by-event in lock-step.

    ``states`` is a batched ``SimState`` whose job table is *preloaded*
    (submit_t/nodes/est_runtime filled for every slot) but entirely
    INVALID: slots become visible to the scheduler only when their
    arrival is injected.  ``arrival_t`` (k, J) is the per-fork arrival
    timeline — non-decreasing along J, ``inf`` on padding slots — and
    ``true_rt`` (k, J) the ground-truth runtimes the scheduler never
    sees.

    Each iteration processes exactly ONE event per live fork, mirroring
    the host emulator's heap semantics bit-for-bit:

      * the next event is ``min(next arrival, next actual end)``;
        arrivals win ties (they were pushed first), simultaneous ends
        retire in start order (push order of their end events);
      * completions retire at ``start + true_rt`` — the *actual* end —
        and overwrite the predicted ``end_t``, while running jobs keep
        advertising ``start + est_runtime`` to the scheduling pass
        (§3.2: the twin schedules against estimates; reality corrects);
      * after the event, one scheduling pass runs on the whole batch
        through the same ``order_fn``/``pass_fn`` stages as the drain.

    A fork with no next event freezes: done if nothing is queued,
    deadlocked if a queued job remains (its request exceeds that fork's
    cluster) — other forks keep stepping either way.  The iteration
    bound is 2·J + 2: every live iteration consumes one arrival or one
    completion (≤ J of each), plus one iteration to flag deadlock.

    Hot-loop compaction (DESIGN.md §7): ``dynamic_bounds`` truncates
    the pass's rank loops at the deepest live queued rank
    (``pass_rank_limit``); ``elide_empty`` wraps keys + argsort + pass
    in a scalar ``lax.cond`` that skips the whole stage on iterations
    where no live fork has a queued job after the event is applied
    (completion-only stretches of sparse traces) — bit-exact, since the
    pass can only ever start queued jobs of live forks.
    """
    k = states.now.shape[0]
    max_jobs = states.jobs.capacity
    max_iters = 2 * max_jobs + 2
    slots = jnp.arange(max_jobs)
    ord_none = jnp.iinfo(jnp.int32).max

    def next_arrival(cursor):
        cur = jnp.clip(cursor, 0, max_jobs - 1)
        t = jnp.take_along_axis(arrival_t, cur[:, None], axis=1)[:, 0]
        return jnp.where(cursor < max_jobs, t, jnp.inf), cur

    def cond(carry):
        st, cursor, true_end, start_ord, it, dead, events, passes = carry
        next_arr, _ = next_arrival(cursor)
        jstate = st.jobs.state
        work = (jnp.isfinite(next_arr)
                | jnp.any(jstate == RUNNING, axis=1)
                | jnp.any(jstate == QUEUED, axis=1))
        return (it < max_iters) & jnp.any(work & ~dead)

    def body(carry):
        st, cursor, true_end, start_ord, it, dead, events, passes = carry
        with jax.named_scope("advance"):
            jobs = st.jobs

            # ---- pick each fork's next event -------------------------
            next_arr, cur = next_arrival(cursor)
            running = jobs.state == RUNNING
            te = jnp.where(running, true_end, jnp.inf)
            next_end = jnp.min(te, axis=1)                   # (k,)
            # among simultaneous actual ends, retire the earliest-started
            # (the host heap pops end events in push == start order)
            at_min = running & (te <= next_end[:, None])
            j_end = jnp.argmin(jnp.where(at_min, start_ord, ord_none),
                               axis=1)

            is_arr = next_arr <= next_end        # equal times: arrival first
            t_ev = jnp.minimum(next_arr, next_end)
            has_event = jnp.isfinite(t_ev)
            dead = dead | (~has_event
                           & jnp.any(jobs.state == QUEUED, axis=1))
            live = has_event & ~dead                         # (k,)

            # ---- inject the arrival (slot = cursor) ------------------
            arr = live & is_arr
            hit_arr = (slots[None, :] == cur[:, None]) & arr[:, None]
            jstate = jnp.where(hit_arr, QUEUED, jobs.state)
            cursor = cursor + arr.astype(jnp.int32)

            # ---- retire the completion at its TRUE end time ----------
            fin = live & ~is_arr
            hit_end = (slots[None, :] == j_end[:, None]) & fin[:, None]
            jstate = jnp.where(hit_end, DONE, jstate)
            end_t = jnp.where(hit_end, true_end, jobs.end_t)
            freed = jnp.sum(jnp.where(hit_end, jobs.nodes, 0), axis=1)
            st = st._replace(
                jobs=jobs._replace(state=jstate, end_t=end_t),
                free_nodes=st.free_nodes + freed,
                now=jnp.where(live, t_ev, st.now),
            )

        # ---- one scheduling pass on the whole batch ------------------
        # Only live forks' starts survive the mask below, so the pass
        # is pure overhead whenever no live fork has a queued job:
        # elide keys + argsort + pass behind one scalar cond.  The rank
        # limit doubles as the elision predicate — limit > 0 iff some
        # live fork has a queued job.
        limit = pass_rank_limit(st, live)

        def run_pass(op):
            st, true_end, start_ord, passes = op
            with jax.named_scope("keys"):
                order = order_fn(st)
            with jax.named_scope("pass"):
                started = pass_fn(st, order,
                                  limit if dynamic_bounds else None)
                started = started & live[:, None]
                st = apply_starts(st, started)
                true_end = jnp.where(started, st.now[:, None] + true_rt,
                                     true_end)
                start_ord = jnp.where(started,
                                      it * (max_jobs + 1) + slots[None, :],
                                      start_ord)
            return st, true_end, start_ord, passes + 1

        op = (st, true_end, start_ord, passes)
        if elide_empty:
            st, true_end, start_ord, passes = jax.lax.cond(
                limit > 0, run_pass, lambda o: o, op)
        else:
            st, true_end, start_ord, passes = run_pass(op)
        return (st, cursor, true_end, start_ord, it + 1, dead,
                events + live.astype(jnp.int32), passes)

    init = (states,
            jnp.zeros((k,), dtype=jnp.int32),
            jnp.full((k, max_jobs), jnp.inf, dtype=jnp.float32),
            jnp.full((k, max_jobs), ord_none, dtype=jnp.int32),
            jnp.int32(0),
            jnp.zeros((k,), dtype=bool),
            jnp.zeros((k,), dtype=jnp.int32),
            jnp.int32(0))
    st, _, _, _, it, dead, events, passes = jax.lax.while_loop(
        cond, body, init)
    return ReplayResult(state=st, events=events, iters=it, deadlocked=dead,
                        pass_invocations=passes)


class DrainMetrics(NamedTuple):
    avg_wait: jax.Array
    max_wait: jax.Array
    avg_slowdown: jax.Array
    max_slowdown: jax.Array
    makespan: jax.Array
    utilization: jax.Array


SLOWDOWN_TAU = 10.0  # bounded-slowdown floor (seconds), standard practice


def drain_metrics(result: DrainResult, eval_mask: jax.Array,
                  runtime: jax.Array | None = None) -> DrainMetrics:
    """User/system metrics over ``eval_mask`` jobs (§3.4: the jobs
    waiting in the queue at decision time).

    ``runtime`` defaults to the estimate (all the twin knows); the
    emulator passes true runtimes when scoring *actual* outcomes.
    """
    return state_metrics(result.state, eval_mask, runtime)


def state_metrics(state: SimState, eval_mask: jax.Array,
                  runtime: jax.Array | None = None) -> DrainMetrics:
    """The same metrics over any final state — replay results score
    with ``runtime`` = ground truth and ``eval_mask`` = the scenario's
    real (non-padding) slots."""
    jobs = state.jobs
    rt = jobs.est_runtime if runtime is None else runtime
    n = jnp.maximum(jnp.sum(eval_mask), 1)

    wait = jnp.where(eval_mask, jobs.start_t - jobs.submit_t, 0.0)
    wait = jnp.maximum(wait, 0.0)
    sd = (wait + rt) / jnp.maximum(rt, SLOWDOWN_TAU)
    sd = jnp.maximum(sd, 1.0)
    sd = jnp.where(eval_mask, sd, 0.0)

    makespan = jnp.max(jnp.where(eval_mask, jobs.end_t, 0.0))
    node_seconds = jnp.sum(jnp.where(eval_mask, jobs.nodes * rt, 0.0))
    span = jnp.maximum(
        makespan - jnp.min(jnp.where(eval_mask, jobs.submit_t, jnp.inf)), 1e-6)
    util = node_seconds / (state.total_nodes.astype(jnp.float32) * span)

    return DrainMetrics(
        avg_wait=jnp.sum(wait) / n,
        max_wait=jnp.max(wait),
        avg_slowdown=jnp.sum(sd) / n,
        max_slowdown=jnp.max(jnp.where(eval_mask, sd, 1.0)),
        makespan=makespan,
        utilization=jnp.clip(util, 0.0, 1.0),
    )


# ----------------------------------------------------------------------
# Distributional reductions over the Monte-Carlo fan axis (DESIGN.md
# §10).  A fan evaluation stacks F perturbed futures per (scenario,
# policy) cell on the fork axis; risk goals reduce per-member costs
# over that axis with ORDER STATISTICS, not moments.  The fan size F is
# static to the jits, so these index computations happen at trace time
# and the device reduction is a plain sort + static gather — bit-exact
# against a numpy ``np.sort`` oracle.
# ----------------------------------------------------------------------

def quantile_index(q: float, n: int) -> int:
    """Nearest-rank quantile index into an ascending sort of ``n``
    values: ``ceil(q·n) - 1`` clamped to ``[0, n-1]``.  Exact order
    statistic (no interpolation): p50 of 4 members is sorted[1], p95 of
    256 is sorted[243]."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile must be in (0, 1], got {q!r}")
    return min(n - 1, max(0, math.ceil(q * n) - 1))


def cvar_tail_count(alpha: float, n: int) -> int:
    """How many worst members the CVaR_α tail averages:
    ``max(1, ceil((1-α)·n))``.  α=0 is the plain mean, α→1 approaches
    the worst case; always >= 1 so the reduction is defined for any F."""
    if not 0.0 <= alpha < 1.0:
        raise ValueError(f"cvar alpha must be in [0, 1), got {alpha!r}")
    return max(1, min(n, math.ceil((1.0 - alpha) * n)))
