"""Parallel what-if exploration (§3.3) — the paper's k simulator forks.

The paper forks k simulator processes (one per policy) sharing a common
database.  On TPU the natural equivalent is a *policy batch axis*: one
batched DES advanced in lock-step for all policies by the
``repro.core.engine.DrainEngine`` (DESIGN.md §3).  The snapshot is
shared (broadcast, never copied per policy) — the same "objects share a
common database, only carry event metadata" property, but in SPMD form.

A pool is a **``PolicyPool``** — a stacked parametric ``PolicySpec``
(family (k,), θ (k, P)) plus per-fork display names (DESIGN.md §5).
Every entry point also accepts a sweep-grammar string (``"paper"``,
``"wfp:a=1..5x5"``), a raw ``PolicySpec`` stack, or a legacy i32 id
vector (``pool_array`` is the thin adapter that builds one); ids flow
through the engine's bit-exact pre-parametric oracle path.

Every entry point takes ``objective=`` — the administrator-configured
optimization goal (``core.objective``, DESIGN.md §8) as an
``Objective`` or grammar string; the deprecated ``weights=`` kwarg
lifts to the bit-identical paper-score objective.

This module is the thin public API over the engine:

  * ``decide`` / ``decide_ensemble`` — one scheduling cycle on the
    default (or a caller-supplied) engine; ensemble members ride the
    same batch axis, so k * n_ens forks drain in ONE while_loop;
  * ``sharded_whatif`` — the fork axis of the batched engine sharded
    over a device mesh for pools of hundreds of forks (θ shards with
    the fork axis: a parameter sweep is just a longer, shardable pool);
  * ``decide_legacy_vmap`` — the pre-engine path (``jax.vmap`` over the
    scalar DES), kept as a regression oracle and as the baseline the
    overhead benchmark compares the batched engine against.
"""
from __future__ import annotations

import functools
import itertools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import scoring
from repro.core.des import drain_metrics, simulate_to_drain
from repro.core.engine import (DEFAULT_ENGINE, Decision, DrainEngine,
                               EnginePool, _quiet_donation)
from repro.core.objective import ObjectiveLike, resolve_goal
from repro.core.policies import (PolicyPool, PolicySpec, normalize_pool,
                                 parse_pool)
from repro.core.state import QUEUED, SimState

__all__ = [
    "Decision", "PolicyPool", "decide", "decide_ensemble",
    "decide_legacy_vmap", "sharded_whatif", "sharded_replay_grid",
    "sharded_fan_grid", "sharded_race_grid", "paper_pool", "pool_array",
]

#: Anything the public decide functions take as a pool.
PoolArg = Union[PolicyPool, PolicySpec, str, jax.Array]


def _engine_pool(pool: PoolArg) -> EnginePool:
    """Unwrap to what the engine consumes: a PolicySpec stack or a
    legacy id vector (passed through untouched — the oracle path)."""
    if isinstance(pool, PolicyPool):
        return pool.spec
    if isinstance(pool, str):
        return parse_pool(pool).spec
    return pool  # PolicySpec stack or legacy id array


def decide(state: SimState, pool: PoolArg,
           objective: ObjectiveLike = None, *,
           weights: Optional[scoring.ScoreWeights] = None,
           engine: Optional[DrainEngine] = None) -> Decision:
    """One scheduling cycle: fork k sims, score, select, extract qrun set.

    ``pool`` is a ``PolicyPool`` / ``PolicySpec`` stack / grammar
    string / legacy i32 id vector, ordered by tie-break priority.
    ``objective`` is the administrator's goal (DESIGN.md §8): an
    ``Objective``, a grammar string (``"score"``, ``"avg_wait"``,
    ``"min:avg_wait@util>=0.85"``), or None for the paper score;
    ``weights=`` is the deprecated legacy spelling (lifted
    bit-identically with a DeprecationWarning).  Everything (all k
    drain simulations included) is a single XLA computation — the
    per-cycle overhead the paper reports as "a few seconds" is
    microseconds here (see benchmarks/overhead.py).
    """
    return (engine or DEFAULT_ENGINE).decide(
        state, _engine_pool(pool), objective, weights=weights)


def decide_ensemble(state: SimState, pool: PoolArg, key: jax.Array,
                    n_ens: int = 8, noise: float = 0.3,
                    objective: ObjectiveLike = None, *,
                    weights: Optional[scoring.ScoreWeights] = None,
                    engine: Optional[DrainEngine] = None) -> Decision:
    """Uncertainty-aware cycle (beyond paper).

    Each ensemble member rescales every job's estimate by a lognormal
    factor (sigma=``noise``) before simulating; the policy cost is the
    ensemble mean (under ``objective``, as in ``decide``).  The qrun
    set is taken from the unperturbed member so actions stay consistent
    with the mirror.  All k * n_ens forks ride one batch axis through
    one drain.
    """
    return (engine or DEFAULT_ENGINE).decide_ensemble(
        state, _engine_pool(pool), key, n_ens=n_ens, noise=noise,
        objective=objective, weights=weights)


# ----------------------------------------------------------------------
# Legacy path: vmap over the scalar DES (pre-engine).  Benchmark /
# regression oracle only — new code should use the engine.
# ----------------------------------------------------------------------

def _single_whatif(state: SimState, policy_id) -> tuple:
    eval_mask = state.jobs.state == QUEUED
    res = simulate_to_drain(state, policy_id)
    m = drain_metrics(res, eval_mask)
    return m, res.first_started, res.deadlocked


@functools.partial(jax.jit, static_argnames=("weights",))
def decide_legacy_vmap(state: SimState, pool: jax.Array,
                       weights: scoring.ScoreWeights = scoring.PAPER_WEIGHTS
                       ) -> Decision:
    metrics, first_started, dead = jax.vmap(
        _single_whatif, in_axes=(None, 0))(state, pool)
    costs = scoring.policy_cost(metrics, weights)
    costs = jnp.where(dead, jnp.inf, costs)
    best = scoring.select_policy(costs)
    return Decision(
        policy_index=best,
        costs=costs,
        run_mask=first_started[best],
        metrics=metrics,
        deadlocked=dead,
    ).packed()


# ----------------------------------------------------------------------
# Fleet scale: shard the fork axis of the batched engine (DESIGN.md §9).
# ----------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("engine", "mesh", "axis", "objective",
                                    "plan"))
def _decide_fleet(engine: DrainEngine, mesh: Mesh, axis: str,
                  state: SimState, pool: EnginePool, objective, plan):
    """The sharded decision cycle: the DRAIN runs per shard under
    ``shard_map`` (each device forks/drains its own chunk of the pool,
    with its own shard-local hoist plan and pass bound), the selection
    (metrics -> costs -> argmin) runs on the concatenated result so the
    winner is global and keeps ``select_policy``'s first-occurrence
    tie-break over the FULL pool order."""
    from repro.core.des import broadcast_state, drain_metrics
    from repro.core.engine import _drain_impl, hoisted_orders, pool_size
    from repro.launch.mesh import shard_map

    n_shards = mesh.shape[axis]
    k_local = pool_size(pool) // n_shards

    if plan is None:
        def local(st: SimState, pool_shard: EnginePool):
            return _drain_impl(engine, broadcast_state(st, k_local),
                               pool_shard, plan)

        res = shard_map(local, mesh, in_specs=(P(), P(axis)),
                        out_specs=P(axis))(state, pool)
    else:
        # Hoisted static argsorts are computed HERE, in the GSPMD region,
        # and cross the shard boundary as a sharded input — jax 0.4
        # miscompiles the sort if it is traced inside the shard_map body
        # (see engine.hoisted_orders).  `plan` is shard-local; the global
        # plan is its n_shards-fold tile, so np.nonzero enumerates each
        # shard's time-invariant rows contiguously and P(axis) hands
        # every device exactly its own rows.
        states_full = broadcast_state(state, k_local * n_shards)
        hoisted = hoisted_orders(states_full, pool, plan * n_shards,
                                 states_full.jobs.state == QUEUED)

        def local(st: SimState, pool_shard: EnginePool, hoist_shard):
            return _drain_impl(engine, broadcast_state(st, k_local),
                               pool_shard, plan, hoisted=hoist_shard)

        res = shard_map(local, mesh, in_specs=(P(), P(axis), P(axis)),
                        out_specs=P(axis))(state, pool, hoisted)

    eval_mask = state.jobs.state == QUEUED
    metrics = jax.vmap(drain_metrics, in_axes=(0, None))(res, eval_mask)
    costs = objective.costs(metrics)
    costs = jnp.where(res.deadlocked, jnp.inf, costs)
    best = scoring.select_policy(costs)
    return Decision(
        policy_index=best,
        costs=costs,
        run_mask=res.first_started[best],
        metrics=metrics,
        deadlocked=res.deadlocked,
        cost_terms=objective.cost_terms(metrics),
    ).packed()


def sharded_whatif(mesh: Mesh, axis: str = "data",
                   engine: Optional[DrainEngine] = None,
                   objective: ObjectiveLike = None, *,
                   weights: Optional[scoring.ScoreWeights] = None):
    """Fleet-scale what-if: the fork (policy/ensemble) axis of the
    batched engine sharded over ``axis`` of ``mesh``.  Returns a jitted
    function with the same signature as ``decide`` whose pool size must
    be divisible by the axis size.  The snapshot is replicated (it is a
    few KB); only the fork axis is split, mirroring "k simulator copies
    sharing one database" at pod scale.

    The pool sharding is a PyTree prefix, so it applies equally to a
    legacy (k,) id vector and to a ``PolicySpec`` stack — for specs the
    θ matrix (k, P) is partitioned on its fork axis together with the
    family vector: a 128-point parameter sweep splits across devices
    exactly like 128 distinct policies.

    Static-key hoisting (DESIGN.md §7) is SHARD-LOCAL here (§9): the
    drain runs per device under ``shard_map``, so each shard hoists the
    argsorts of its own chunk's time-invariant forks — no cross-shard
    regrouping, the same gather/compact as the local engine, applied to
    a shorter fork axis.  ``engine.shard_local_plan`` derives the local
    plan; when the shards' chunks differ (SPMD traces one program) it
    falls back to per-event sorting, bit-identical either way.  The
    dynamic pass bound is likewise shard-local: a deep queue on one
    shard no longer widens every other shard's pass.
    """
    from repro.core.engine import pool_size, shard_local_plan

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective, weights)
    n_shards = mesh.shape[axis]

    def wrapper(state: SimState, pool: PoolArg) -> Decision:
        pool = _engine_pool(pool)
        k = pool_size(pool)
        if k % n_shards:
            raise ValueError(
                f"pool size k={k} not divisible by {n_shards}-way "
                f"'{axis}' axis")
        plan = shard_local_plan(eng.plan(pool), n_shards)
        return _decide_fleet(eng, mesh, axis, state, pool, goal, plan)

    return wrapper


@_quiet_donation
@functools.partial(jax.jit,
                   static_argnames=("engine", "mesh", "axis", "plan"),
                   donate_argnames=("states",))
def _replay_block_sharded(engine: DrainEngine, mesh: Mesh, axis: str,
                          plan, states, arrival_t, true_rt, pool, valid):
    """One fixed-shape scenario block replayed under ``shard_map``:
    every leading (k = B·P) axis splits over ``axis``, each device
    drains its B/n_shards scenarios with the shard-local hoist plan and
    its own pass bound / elision / early-exit (no collectives inside
    the event loop — shards finish independently).  The scalar
    telemetry (``iters``/``pass_invocations``) is lifted to (1,) per
    shard so the stacked output carries one count per device; the
    streamer sums them.  ``states`` is donated — the per-block carry
    updates in place across the stream."""
    from repro.core.engine import _replay_impl, hoisted_orders
    from repro.launch.mesh import shard_map

    if plan is None:
        def local(states, arrival_t, true_rt, pool, valid):
            res, metrics = _replay_impl(engine, states, arrival_t,
                                        true_rt, pool, valid, plan)
            res = res._replace(
                iters=res.iters.reshape(1),
                pass_invocations=res.pass_invocations.reshape(1))
            return res, metrics

        return shard_map(local, mesh, in_specs=(P(axis),) * 5,
                         out_specs=P(axis))(states, arrival_t, true_rt,
                                            pool, valid)

    # Hoisting on: the static argsorts cross the shard boundary as a
    # sharded input (engine.hoisted_orders — jax 0.4 miscompiles them
    # when traced inside the shard_map body).  `plan` is shard-local
    # and periodic, so its n_shards-fold tile is the global plan and
    # P(axis) gives each device its own forks' rows.
    ever_q = jnp.isfinite(arrival_t) | (states.jobs.state == QUEUED)
    hoisted = hoisted_orders(states, pool, plan * mesh.shape[axis],
                             ever_q)

    def local(states, arrival_t, true_rt, pool, valid, hoist_shard):
        res, metrics = _replay_impl(engine, states, arrival_t, true_rt,
                                    pool, valid, plan,
                                    hoisted=hoist_shard)
        res = res._replace(
            iters=res.iters.reshape(1),
            pass_invocations=res.pass_invocations.reshape(1))
        return res, metrics

    return shard_map(local, mesh, in_specs=(P(axis),) * 6,
                     out_specs=P(axis))(states, arrival_t, true_rt,
                                        pool, valid, hoisted)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def sharded_replay_grid(mesh: Mesh, axis: str = "data",
                        engine: Optional[DrainEngine] = None,
                        objective: ObjectiveLike = None, *,
                        weights: Optional[scoring.ScoreWeights] = None,
                        block_size: Optional[int] = None,
                        prefetch_depth: int = 2):
    """Fleet-scale replay: the SCENARIO axis of ``engine.replay_grid``
    sharded over ``axis`` of ``mesh`` and STREAMED in fixed-size blocks
    (DESIGN.md §9).

    The flat fork axis is f = s·P + p, so sharding the leading axis of
    every input by blocks keeps each scenario's P policy forks on one
    device — scenarios are the unit of partition, the natural layout
    for multi-host what-if farms (each host replays its own futures).

    **Block streaming** — ``block_size`` (scenarios per device step;
    rounded up to the axis size) bounds every device computation: an
    S=1024 × P=100 grid runs as a pipeline of identical (B·P, J)
    replays — ONE compiled shape, donated buffers — instead of one
    monolithic 102 400-fork allocation.  ``None`` keeps the single-shot
    behavior (one block of the whole set).  Any S works on any mesh:
    the scenario axis is padded internally to the block multiple with
    inert rows (``workload.pad_scenarios`` — born-drained forks that
    never touch real forks' dynamics) and padded rows are dropped
    before selection.

    **Host/device overlap** — with ``prefetch_depth > 0`` the host-side
    ingestion of block i+1 (slicing/padding — and, for iterable
    sources, whatever synthesis the iterable performs) runs on a
    background thread (``data.pipeline.prefetch``) while the device
    drains block i; ``prefetch_depth=0`` ingests inline and blocks on
    every device step (the ablation baseline).  The ingest thread is
    numpy-only by design: a jax dispatch there (e.g. the jitted
    ``replay_inputs`` tiling) blocks on the in-flight replay and
    re-serializes the pipeline, so the device conversion runs on the
    main thread between dispatches.  Results are bit-identical at any
    depth.

    **Shard-local hoisting** — the replay's hoist plan is periodic in P
    (one pool copy per scenario), so every shard's chunk is the same
    ``plan_P * (B / n_shards)``: each device hoists its own forks'
    static argsorts exactly as the local engine does (DESIGN.md §7),
    composing the compaction win with sharding bit-exactly.

    ``scenarios`` may be a ``workload.ScenarioSet`` or an ITERABLE of
    them (pre-cut blocks, e.g. generated on the fly — trace synthesis
    then overlaps with device compute too).  Iterable blocks share one
    job capacity J; each is padded up to the block size.

    Returns a function ``(scenarios, pool) -> ReplayOutcome`` with the
    same semantics as ``replay_grid``, including the per-objective
    ``costs``/``best`` selection; ``iters``/``pass_invocations`` on the
    raw result aggregate over (shard, block).
    """
    from repro.core.des import ReplayResult
    from repro.core.engine import (_shape_outcome, as_pool,
                                   grid_select_jit, pool_size,
                                   replay_inputs)
    from repro.cluster.workload import (ScenarioSet, pad_scenarios,
                                        slice_scenarios)
    from repro.data.pipeline import prefetch

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective, weights)
    n_shards = mesh.shape[axis]

    def wrapper(scenarios, pool: PoolArg):
        pool = as_pool(_engine_pool(pool))
        Psz = pool_size(pool)
        plan_P = eng.plan(pool)          # per-scenario chunk (hoisting)

        if isinstance(scenarios, ScenarioSet):
            S_real = scenarios.n_scenarios
            B = _round_up(block_size or S_real, n_shards)
            raw = (slice_scenarios(scenarios, lo, min(lo + B, S_real))
                   for lo in range(0, S_real, B))
        else:
            raw = iter(scenarios)
            try:
                head = next(raw)
            except StopIteration:
                raise ValueError("no scenario blocks") from None
            B = _round_up(block_size or head.n_scenarios, n_shards)
            raw = itertools.chain([head], raw)
            S_real = None                # discovered while streaming

        plan_blk = (plan_P * (B // n_shards)
                    if plan_P is not None else None)
        n_reals: list = []

        def ingest():
            # numpy ONLY in this thread: jax dispatch (the jitted
            # tiling in replay_inputs) blocks on the in-flight replay,
            # which would serialize ingestion with device compute —
            # the conversion runs on the main thread below instead
            for blk in raw:
                n = blk.n_scenarios
                if n > B:
                    raise ValueError(
                        f"scenario block of {n} > block size {B}")
                n_reals.append(n)
                yield pad_scenarios(blk, B)

        stream = ingest()
        if prefetch_depth > 0:
            stream = prefetch(stream, depth=prefetch_depth)

        res_blocks, met_blocks = [], []
        for padded in stream:
            res, metrics = _replay_block_sharded(
                eng, mesh, axis, plan_blk,
                *replay_inputs(padded, pool))
            if prefetch_depth <= 0:
                jax.block_until_ready((res, metrics))
            n_keep = n_reals[len(res_blocks)] * Psz
            if n_keep != B * Psz:        # only partial blocks pay a trim
                trim = lambda x: x[:n_keep]
                res = res._replace(
                    state=jax.tree.map(trim, res.state),
                    events=trim(res.events),
                    deadlocked=trim(res.deadlocked))
                metrics = jax.tree.map(trim, metrics)
            res_blocks.append(res)
            met_blocks.append(metrics)
        if not res_blocks:
            raise ValueError("no scenario blocks")
        S_out = sum(n_reals)

        cat = (lambda *xs: xs[0] if len(xs) == 1
               else jnp.concatenate(xs, axis=0))
        res = ReplayResult(
            state=jax.tree.map(cat, *[r.state for r in res_blocks]),
            events=cat(*[r.events for r in res_blocks]),
            iters=sum(r.iters.sum() for r in res_blocks),
            deadlocked=cat(*[r.deadlocked for r in res_blocks]),
            pass_invocations=sum(r.pass_invocations.sum()
                                 for r in res_blocks))
        metrics = jax.tree.map(cat, *met_blocks)
        costs, best = grid_select_jit(goal, metrics, res.deadlocked, Psz)
        return _shape_outcome(res, metrics, (S_out, Psz), costs, best)

    return wrapper


@functools.partial(jax.jit,
                   static_argnames=("spec", "P", "B", "S"))
def _fan_block_inputs(submit, nodes, est, true_rt, valid, totals, pool,
                      spec, P, B, S, lo):
    """One fixed-shape fan block, expanded ON DEVICE: pseudo-scenarios
    ``g = lo .. lo+B`` (``g = s·F + φ``; ids past S·F are inert
    padding) perturbed from the shared base arrays and assembled into
    donatable (B·P)-fork replay inputs.  ``lo`` is a dynamic operand —
    every block reuses ONE compiled expansion."""
    from repro.core.engine import _assemble_replay_inputs
    from repro.core.fan import perturb_block
    g = lo + jnp.arange(B)
    rows = perturb_block(submit, nodes, est, true_rt, valid, totals,
                         spec, g, S)
    return _assemble_replay_inputs(*rows, pool, P)


def sharded_fan_grid(mesh: Mesh, axis: str = "data",
                     engine: Optional[DrainEngine] = None,
                     objective: ObjectiveLike = None, *,
                     fan=None,
                     block_size: Optional[int] = None):
    """Fleet-scale Monte-Carlo fans (DESIGN.md §§9–10): the
    ``engine.fan_grid`` pseudo-scenario axis (``g = s·F + φ``, G = S·F
    rows) sharded over ``axis`` of ``mesh`` and streamed in fixed-size
    blocks, exactly like ``sharded_replay_grid`` streams scenarios.

    The fan stacks on the PR-6 block machinery unchanged because fan
    members ARE pseudo-scenarios: hoist plans stay P-periodic
    (``plan_P · (B / n_shards)`` per shard), padding rows are inert,
    and ``_replay_block_sharded`` is reused as is.  What changes is
    ingestion: there is NO host ingest thread to overlap — each block
    is expanded on device from the one uploaded base (H2D stays O(1)
    in F), so blocks dispatch back-to-back and jax's async dispatch
    pipelines them.  Fan member draws are keyed per (s, φ)
    independently of the block cut, so any ``block_size`` is
    bit-identical to the one-shot ``fan_grid``.

    ``fan`` is a ``FanSpec`` (or bare int F); ``block_size`` counts
    pseudo-scenarios per device step (i.e. ``block_size // F`` base
    scenarios), rounded up to the axis size.  Returns a function
    ``(scenarios, pool) -> FanOutcome``.
    """
    from repro.core.des import ReplayResult
    from repro.core.engine import (FanOutcome, _scenario_arrays, as_pool,
                                   fan_select_jit, pool_size)
    from repro.core.fan import normalize_fan

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective)
    spec = normalize_fan(fan if fan is not None else 1)
    n_shards = mesh.shape[axis]

    def wrapper(scenarios, pool: PoolArg) -> "FanOutcome":
        pool = as_pool(_engine_pool(pool))
        Psz = pool_size(pool)
        S = int(scenarios.total_nodes.shape[0])
        G = S * spec.n
        B = _round_up(block_size or G, n_shards)
        plan_P = eng.plan(pool)
        plan_blk = (plan_P * (B // n_shards)
                    if plan_P is not None else None)
        base = _scenario_arrays(scenarios)

        res_blocks, met_blocks = [], []
        for lo in range(0, G, B):
            inputs = _fan_block_inputs(*base, pool, spec, Psz, B, S,
                                       jnp.int32(lo))
            res, metrics = _replay_block_sharded(
                eng, mesh, axis, plan_blk, *inputs)
            n_keep = (min(lo + B, G) - lo) * Psz
            if n_keep != B * Psz:        # only the tail block pays a trim
                trim = lambda x: x[:n_keep]
                res = res._replace(
                    state=jax.tree.map(trim, res.state),
                    events=trim(res.events),
                    deadlocked=trim(res.deadlocked))
                metrics = jax.tree.map(trim, metrics)
            res_blocks.append(res)
            met_blocks.append(metrics)

        cat = (lambda *xs: xs[0] if len(xs) == 1
               else jnp.concatenate(xs, axis=0))
        res = ReplayResult(
            state=jax.tree.map(cat, *[r.state for r in res_blocks]),
            events=cat(*[r.events for r in res_blocks]),
            iters=sum(r.iters.sum() for r in res_blocks),
            deadlocked=cat(*[r.deadlocked for r in res_blocks]),
            pass_invocations=sum(r.pass_invocations.sum()
                                 for r in res_blocks))
        metrics = jax.tree.map(cat, *met_blocks)
        member, costs, best, ci, width = fan_select_jit(
            goal, metrics, res.deadlocked, spec.n, Psz)
        shape = (S, spec.n, Psz)
        rs = lambda x: x.reshape(shape + x.shape[1:])
        return FanOutcome(
            start_t=rs(res.state.jobs.start_t),
            end_t=rs(res.state.jobs.end_t),
            metrics=jax.tree.map(rs, metrics),
            deadlocked=rs(res.deadlocked),
            events=rs(res.events),
            result=res,
            member_costs=member,
            costs=costs,
            best=best,
            cost_ci=ci,
            fan_width=width,
        )

    return wrapper


def sharded_generation_costs(mesh: Mesh, axis: str = "data",
                             engine: Optional[DrainEngine] = None,
                             objective: ObjectiveLike = None, *,
                             fan=None,
                             block_size: Optional[int] = None,
                             prefetch_depth: int = 2):
    """Fleet-scale generation evaluation for the ``learn`` trainer:
    the sharded twin of ``engine.generation_costs``.  Returns a
    function ``(scenarios, pool) -> (S, P) costs`` — one candidate
    population riding the fork axis, streamed over the mesh via
    ``sharded_replay_grid`` (``fan=None``) or ``sharded_fan_grid``
    (FanSpec domain randomization), both bit-identical to the
    one-shot engine entry point."""
    if fan is None:
        run = sharded_replay_grid(mesh, axis, engine, objective,
                                  block_size=block_size,
                                  prefetch_depth=prefetch_depth)
    else:
        run = sharded_fan_grid(mesh, axis, engine, objective, fan=fan,
                               block_size=block_size)
    return lambda scenarios, pool: run(scenarios, pool).costs


@functools.partial(jax.jit,
                   static_argnames=("spec", "P", "B", "S", "lo", "width"))
def _race_block_inputs(submit, nodes, est, true_rt, valid, totals, pool,
                       spec, P, B, S, lo, width, blo):
    """One fixed-shape RACING-WINDOW block, expanded on device: window
    rows ``r = blo .. blo+B`` of the rung's ``S·width`` rectangle
    (``r = s·width + w`` ⇒ member ``φ = lo + w``; ids past S·width are
    inert padding).  ``lo``/``width`` are STATIC — the rung schedule is
    fixed, so each rung compiles once — while ``blo`` is a dynamic
    operand: all blocks within a rung share one compiled expansion."""
    from repro.core.engine import _assemble_replay_inputs
    from repro.core.fan import perturb_window
    r = blo + jnp.arange(B)
    rows = perturb_window(submit, nodes, est, true_rt, valid, totals,
                          spec, r, lo, width, S)
    return _assemble_replay_inputs(*rows, pool, P)


def sharded_race_grid(mesh: Mesh, axis: str = "data",
                      engine: Optional[DrainEngine] = None,
                      objective: ObjectiveLike = None, *,
                      race=None,
                      block_size: Optional[int] = None):
    """Fleet-scale adaptive racing (DESIGN.md §§9–11): each rung of the
    successive-halving race streams its ``S·width`` member-window rows
    through the PR-6 block machinery (``_replay_block_sharded``
    unchanged — window rows are pseudo-scenarios like any other), and
    the controller (``race.run_race``) eliminates/terminates between
    rungs exactly as the local ``race_grid`` does.

    Because window rows are keyed per (s, φ) independently of the
    block cut AND of the rung cut, any ``block_size`` on any mesh is
    bit-identical to the local race — which is itself member-bitwise
    the full ``fan_grid`` prefix (tests/test_race.py).  ``race`` is a
    ``RaceSpec`` / ``FanSpec`` / bare int F_max; ``block_size`` counts
    window rows per device step, rounded up to the axis size.  Returns
    a function ``(scenarios, pool) -> race.RaceOutcome``.
    """
    from repro.core.des import ReplayResult
    from repro.core.engine import (_index_pool, _scenario_arrays, as_pool,
                                   fan_select_jit, pool_size)
    from repro.core.race import normalize_race, run_race

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective)
    spec = normalize_race(race if race is not None else 1)
    n_shards = mesh.shape[axis]

    def wrapper(scenarios, pool: PoolArg):
        pool_full = as_pool(_engine_pool(pool))
        Psz = pool_size(pool_full)
        S = int(scenarios.total_nodes.shape[0])
        base = _scenario_arrays(scenarios)
        sub_pools = {}
        passes = [0]

        def eval_window(active, lo, hi):
            key = tuple(int(i) for i in active)
            sub = sub_pools.get(key)
            if sub is None:
                sub = (pool_full if len(active) == Psz
                       else _index_pool(pool_full, jnp.asarray(active)))
                sub_pools[key] = sub
            Pa = pool_size(sub)
            width = hi - lo
            R = S * width                      # window rows this rung
            B = _round_up(block_size or R, n_shards)
            plan_P = eng.plan(sub)
            plan_blk = (plan_P * (B // n_shards)
                        if plan_P is not None else None)
            met_blocks, dead_blocks = [], []
            for blo in range(0, R, B):
                inputs = _race_block_inputs(*base, sub, spec.fan, Pa,
                                            B, S, lo, width,
                                            jnp.int32(blo))
                res, metrics = _replay_block_sharded(
                    eng, mesh, axis, plan_blk, *inputs)
                passes[0] += int(res.pass_invocations.sum())
                n_keep = (min(blo + B, R) - blo) * Pa
                if n_keep != B * Pa:     # only the tail block trims
                    trim = lambda x: x[:n_keep]
                    metrics = jax.tree.map(trim, metrics)
                    dead_blocks.append(trim(res.deadlocked))
                else:
                    dead_blocks.append(res.deadlocked)
                met_blocks.append(metrics)
            cat = (lambda *xs: xs[0] if len(xs) == 1
                   else jnp.concatenate(xs, axis=0))
            metrics = jax.tree.map(cat, *met_blocks)
            dead = cat(*dead_blocks)
            member, _, _, _, _ = fan_select_jit(
                goal, metrics, dead, width, Pa)
            return member

        out = run_race(spec, S, Psz, goal, eval_window)
        return out._replace(passes=passes[0])

    return wrapper


def paper_pool() -> jax.Array:
    from repro.core.policies import PAPER_POOL
    return jnp.asarray(PAPER_POOL, dtype=jnp.int32)


def pool_array(ids: Sequence[int]) -> jax.Array:
    """Thin adapter: legacy id pool in the CALLER's order.  Position is
    tie-break priority (``select_policy`` is an argmin with
    first-occurrence wins), so the order must be preserved — an earlier
    version sorted ids here, silently discarding custom tie-break
    orders.  ``policies.PolicyPool.from_ids`` lifts the same ids into
    the parametric space."""
    return jnp.asarray(list(ids), dtype=jnp.int32)
