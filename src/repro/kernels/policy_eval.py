"""Pallas TPU kernel for the scheduling pass — the paper's hot spot.

Every SchedTwin cycle runs k drain simulations; each simulation runs a
*scheduling pass* (priority order + greedy starts + EASY backfill) at
every event.  The paper parallelizes this with k CQSim processes on 48
CPU cores; the TPU-native adaptation is a **policy-batched kernel**:

  * grid = the policy/ensemble axis in tiles of ``TILE`` = 8 forks (one
    sublane tile), so every (8, J) block obeys Mosaic's (8, 128) block
    rule and one vector op advances all eight forks at once,
  * the queue state (<= max_jobs jobs x 6 f32 fields, ~6 KB per fork at
    J=256) is VMEM-resident for the whole pass,
  * the inherently sequential greedy/backfill dependence is an
    in-kernel ``fori_loop`` over priority ranks.  Mosaic has no scalar
    gather from a vector value, so each rank step reads its job with a
    masked lane reduction over an iota (``_pick``: the slot at rank i,
    then that slot's fields) and records a start as a masked vector
    select — exact, since exactly one lane is selected,
  * the EASY "shadow time" is computed WITHOUT the CPU algorithm's
    sort: for every candidate end time t_j we evaluate
    ``free_at(t_j) = free + sum(nodes_r * (end_r <= t_j))`` — an O(J^2)
    SIMD broadcast-reduce (one (J, J) f32 block per fork, 256 KiB at
    J=256, reduced over sublanes on the VPU) that replaces an
    O(J log J) sort-scan.  Node counts are integers, so the sum is
    exact in any order.  See ``DESIGN.md`` §2 (hardware adaptation) at
    the repo root for the full derivation and the tie-handling caveat.

Two entry points:
  * ``policy_eval_pass`` — shared snapshot, per-policy ``order`` only
    (the first pass of a decision cycle, where all forks still share
    one queue state): a broadcast onto the batched entry;
  * ``policy_eval_pass_batched`` — every input carries the fork axis
    (mid-drain, after fork states have diverged).  This is the
    ``pallas`` backend of ``repro.core.engine.DrainEngine``.

The wrappers pad the fork axis to a multiple of 8 and the job axis to
a multiple of 128 with inert rows and slots (never queued, never
running), then slice the padding off — bit-exact.

``interpret`` has no default: callers resolve it from the platform
(``default_interpret``: compiled on TPU, interpreted elsewhere).

The priority *keys* are computed (and argsorted) outside the kernel —
they are embarrassingly parallel and XLA already fuses them; the kernel
owns the sequential part.

Inputs (policy axis k leading where applicable):
  order     (k, J) i32   — job slots in priority order (invalid last)
  queued    (J,)   i32   — 1 if job is QUEUED
  nodes     (J,)   f32   — node request per job
  est       (J,)   f32   — user walltime estimate
  run_end   (J,)   f32   — predicted end for RUNNING jobs else +inf
  run_nodes (J,)   f32   — nodes held by RUNNING jobs else 0
  free0     ()     f32   — free nodes now
  now       ()     f32   — current time
  limit     ()     i32   — rank bound for both sequential loops: ranks
                           in [limit, J) hold no queued slot, so the
                           greedy and backfill ``fori_loop``s stop there
                           (a dynamic trip count read from SMEM;
                           bit-exact, see DESIGN.md §7).  None = J.

Outputs:
  started (k, J) i32 — jobs started by this pass under each policy
  free    (k,)   f32 — free nodes after the pass
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38  # ~f32 inf stand-in (pallas-friendly)
TILE = 8      # forks per grid program: one f32/i32 sublane tile
LANES = 128   # the job axis is padded to whole lane tiles


def default_interpret() -> bool:
    """Pallas interpret mode off the TPU, the compiled kernel on it."""
    return jax.default_backend() != "tpu"


def _pick(mask: jax.Array, x: jax.Array) -> jax.Array:
    """Per row, ``x`` at the single lane where ``mask`` holds -> (T, 1).
    A masked sum over one selected lane: exact for ints and floats."""
    return jnp.sum(jnp.where(mask, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _free_at(end_eff: jax.Array, nodes_eff: jax.Array) -> jax.Array:
    """``out[r, i] = sum_j nodes_eff[r, j] * (end_eff[r, j] <= end_eff[r, i])``
    for every fork row r of the tile: one (J, J) compare per row, with
    j on sublanes so the reduction is a VPU sum over vregs."""
    end_t = end_eff.T                                   # (J, T)
    nodes_t = nodes_eff.T
    sub = jax.lax.broadcasted_iota(jnp.int32, end_eff.shape, 0)
    out = jnp.zeros_like(end_eff)
    for r in range(end_eff.shape[0]):
        le = end_t[:, r:r + 1] <= end_eff[r:r + 1, :]  # (J, J): [j, i]
        row = jnp.sum(jnp.where(le, nodes_t[:, r:r + 1], 0.0), axis=0,
                      keepdims=True)                    # (1, J)
        out = jnp.where(sub == r, row, out)
    return out


def _pass_kernel(limit_ref, order_ref, queued_ref, nodes_ref, est_ref,
                 run_end_ref, run_nodes_ref, free_ref, now_ref,
                 started_ref, free_out_ref):
    """One scheduling pass for a tile of TILE forks (grid dim 0)."""
    order = order_ref[...]           # (T, J) i32 priority-ranked job ids
    queued = queued_ref[...]         # (T, J) i32
    nodes = nodes_ref[...]           # (T, J) f32
    est = est_ref[...]
    run_end = run_end_ref[...]
    run_nodes = run_nodes_ref[...]
    free0 = free_ref[...]            # (T, 1) f32
    now = now_ref[...]               # (T, 1) f32
    j_cap = order.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, order.shape, 1)
    # rank bound: ranks >= limit hold no queued slot -> provable no-ops
    # in both sequential loops below (truncation is bit-exact)
    limit = jnp.minimum(limit_ref[0, 0], j_cap)

    def at_rank(i):
        """(slot mask (T, J), is_queued (T, 1), nodes (T, 1)) of the
        job each fork ranks at ``i``."""
        slot = lane == _pick(lane == i, order)
        return slot, _pick(slot, queued) > 0, _pick(slot, nodes)

    # ---- pass 1: greedy in priority order (sequential) ---------------
    def greedy(i, carry):
        free, head_rank, started = carry
        slot, is_queued, need = at_rank(i)
        fits = jnp.where(is_queued, need, BIG) <= free  # invalid never fit
        no_head = head_rank < 0
        start = fits & no_head & is_queued
        free = jnp.where(start, free - need, free)
        started = jnp.where(slot & start, 1, started)
        blocked = is_queued & (~fits) & no_head
        head_rank = jnp.where(blocked, i, head_rank)
        return free, head_rank, started

    free1, head_rank, started1 = jax.lax.fori_loop(
        0, limit, greedy,
        (free0, jnp.full(free0.shape, -1, jnp.int32),
         jnp.zeros(order.shape, jnp.int32)))

    has_head = head_rank >= 0
    head = _pick(lane == jnp.maximum(head_rank, 0), order)
    head_nodes = jnp.where(has_head, _pick(lane == head, nodes), 0.0)

    # ---- shadow time without a sort (O(J^2) SIMD) ---------------------
    # running set = RUNNING jobs + jobs started in pass 1 (their end is
    # now + estimate; the twin never sees true runtimes).
    end_eff = jnp.where(started1 > 0, now + est, run_end)       # (T, J)
    nodes_eff = jnp.where(started1 > 0, nodes, run_nodes)       # (T, J)
    free_at = free1 + _free_at(end_eff, nodes_eff)              # (T, J)
    feasible = (free_at >= head_nodes) & (end_eff < BIG)
    t_cand = jnp.where(feasible, end_eff, BIG)
    shadow = jnp.where(has_head, jnp.min(t_cand, axis=1, keepdims=True),
                       BIG)
    at_shadow = feasible & (end_eff <= shadow)
    any_at = jnp.max(at_shadow.astype(jnp.int32), axis=1,
                     keepdims=True) > 0
    extra_raw = jnp.max(jnp.where(at_shadow, free_at, -BIG), axis=1,
                        keepdims=True) - head_nodes
    extra = jnp.where(has_head, jnp.where(any_at, extra_raw, 0.0), BIG)

    # ---- pass 2: EASY backfill (sequential) ---------------------------
    def backfill(i, carry):
        free, extra, started = carry
        slot, is_queued, need = at_rank(i)
        cand = is_queued & (_pick(slot, started) == 0) & (i != head_rank)
        fits_now = need <= free
        cond_a = (now + _pick(slot, est)) <= shadow
        cond_b = need <= extra
        start = cand & fits_now & (cond_a | cond_b)
        free = jnp.where(start, free - need, free)
        extra = jnp.where(start & (~cond_a), extra - need, extra)
        started = jnp.where(slot & start, 1, started)
        return free, extra, started

    # ranks <= head_rank cannot backfill (started in pass 1, or the head
    # itself); no head -> nothing left to backfill at all.  The tile
    # starts at its shallowest fork's bound: the forks whose bound lies
    # deeper find no candidate at the ranks in between, so their carry
    # is unchanged there.
    back_lo = jnp.min(jnp.where(has_head, head_rank + 1, limit))
    free2, _, started = jax.lax.fori_loop(
        back_lo, limit, backfill, (free1, extra, started1))

    started_ref[...] = started
    free_out_ref[...] = free2


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _limit_arr(limit, j_cap: int) -> jax.Array:
    """(1, 1) i32 rank bound; ``None`` -> the full static bound J."""
    if limit is None:
        limit = j_cap
    return jnp.asarray(limit, dtype=jnp.int32).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def policy_eval_pass_batched(order: jax.Array, queued: jax.Array,
                             nodes: jax.Array, est: jax.Array,
                             run_end: jax.Array, run_nodes: jax.Array,
                             free0: jax.Array, now: jax.Array,
                             limit: jax.Array | None = None,
                             *, interpret: bool):
    """Fully policy-batched scheduling pass: ALL inputs are (k, J)
    (``free0``/``now`` are (k,)) — one row per fork, TILE forks per
    grid program.  Used inside the batched drain, where fork states
    have diverged (different jobs running, different clocks,
    ensemble-perturbed estimates).

    Returns (started (k, J) i32, free (k,) f32).  ``limit`` (i32
    scalar, shared by the grid — the engine's ``pass_rank_limit``)
    truncates the sequential rank loops; None scans all J ranks.
    """
    k, j_cap = order.shape
    kp, jp = _round_up(k, TILE), _round_up(j_cap, LANES)
    f32 = jnp.float32

    def rows(x, dtype, fill):                 # (k, J) -> (kp, jp)
        return jnp.pad(x.astype(dtype), ((0, kp - k), (0, jp - j_cap)),
                       constant_values=fill)

    def col(x):                               # (k,) -> (kp, 1)
        return jnp.pad(x.astype(f32).reshape(k, 1), ((0, kp - k), (0, 0)))

    # padded ranks hold the padded (never queued) slots
    order_p = jnp.concatenate(
        [order.astype(jnp.int32),
         jnp.broadcast_to(jnp.arange(j_cap, jp, dtype=jnp.int32),
                          (k, jp - j_cap))], axis=1)
    order_p = jnp.pad(order_p, ((0, kp - k), (0, 0)))

    row_spec = pl.BlockSpec((TILE, jp), lambda p: (p, 0))
    col_spec = pl.BlockSpec((TILE, 1), lambda p: (p, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    started, free = pl.pallas_call(
        _pass_kernel,
        grid=(kp // TILE,),
        in_specs=[smem] + [row_spec] * 6 + [col_spec, col_spec],
        out_specs=[row_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((kp, jp), jnp.int32),
            jax.ShapeDtypeStruct((kp, 1), f32),
        ],
        interpret=interpret,
    )(_limit_arr(limit, j_cap),
      order_p,
      rows(queued, jnp.int32, 0),
      rows(nodes, f32, 0.0),
      rows(est, f32, 0.0),
      rows(run_end, f32, jnp.inf),
      rows(run_nodes, f32, 0.0),
      col(free0),
      col(now))
    return started[:k, :j_cap], free[:k, 0]


def policy_eval_pass(order: jax.Array, queued: jax.Array,
                     nodes: jax.Array, est: jax.Array,
                     run_end: jax.Array, run_nodes: jax.Array,
                     free0: jax.Array, now: jax.Array,
                     limit: jax.Array | None = None,
                     *, interpret: bool):
    """Shared-snapshot scheduling pass: ``order`` is (k, J), the rest
    (J,) / scalars, broadcast onto ``policy_eval_pass_batched``.

    Returns (started (k, J) i32, free (k,) f32).  ``limit`` (i32
    scalar) truncates the sequential rank loops; None scans all J
    ranks.
    """
    k, j_cap = order.shape
    rows = lambda x: jnp.broadcast_to(x, (k, j_cap))  # noqa: E731
    fork = lambda x: jnp.broadcast_to(jnp.reshape(x, ()), (k,))  # noqa: E731
    return policy_eval_pass_batched(
        order, rows(queued), rows(nodes), rows(est), rows(run_end),
        rows(run_nodes), fork(free0), fork(now), limit,
        interpret=interpret)
