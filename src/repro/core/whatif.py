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
  * ``sharded_whatif`` — the engine's decision program with its fork
    axis sharded over a device mesh, for pools of hundreds of forks
    (θ shards with the fork axis: a parameter sweep is just a longer,
    shardable pool);
  * ``sharded_replay_grid`` / ``sharded_fan_grid`` /
    ``sharded_race_grid`` — replay, fan and racing grids streamed over
    a mesh in fixed-size blocks by ONE block streamer
    (``_stream_blocks``); a fan is one member window of blocks
    expanded on device, a race one window per rung;
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
from jax.sharding import Mesh

from repro.core import scoring
from repro.core.des import drain_metrics, simulate_to_drain
from repro.core.engine import (DEFAULT_ENGINE, Decision, DrainEngine,
                               EnginePool, _quiet_donation)
from repro.core.objective import ObjectiveLike, resolve_goal
from repro.core.policies import PolicyPool, PolicySpec, parse_pool
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

def sharded_whatif(mesh: Mesh, axis: str = "data",
                   engine: Optional[DrainEngine] = None,
                   objective: ObjectiveLike = None, *,
                   weights: Optional[scoring.ScoreWeights] = None):
    """Fleet-scale what-if: the fork (policy/ensemble) axis of the
    batched engine sharded over ``axis`` of ``mesh``.  Returns a jitted
    function with the same signature as ``decide`` whose pool size must
    be divisible by the axis size.  The snapshot is replicated (it is a
    few KB) and broadcast to the forks; only the fork axis is split,
    mirroring "k simulator copies sharing one database" at pod scale.
    It is ``engine._decide`` with a ``(mesh, axis)`` placement: the
    same member program and reduction as the local decide.

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
    from repro.core.engine import _decide, pool_size

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
        return _decide(eng, state, pool, goal, eng.plan(pool),
                       placement=(mesh, axis))

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
    from repro.core.engine import _replay_impl, on_shards

    def local(states, pool, arrival_t, true_rt, valid, hoisted=None):
        res, metrics = _replay_impl(engine, states, arrival_t, true_rt,
                                    pool, valid, plan, hoisted=hoisted)
        return res._replace(
            iters=res.iters.reshape(1),
            pass_invocations=res.pass_invocations.reshape(1)), metrics

    ever_q = jnp.isfinite(arrival_t) | (states.jobs.state == QUEUED)
    return on_shards(local, mesh, axis, plan, ever_q, states, pool,
                     arrival_t, true_rt, valid)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _stream_blocks(engine: DrainEngine, mesh: Mesh, axis: str,
                   pool: EnginePool, B: int, blocks, sync: bool = False):
    """The one block streamer (DESIGN.md §9): each ``(inputs, rows)`` of
    ``blocks`` is one fixed-shape block of B rows (scenarios or fan
    rows, P forks each) replayed through ``_replay_block_sharded``,
    of which the first ``rows`` are real.  A partial block's padding
    is trimmed, the blocks join on the fork axis, and ``iters`` /
    ``pass_invocations`` sum over (shard, block).  Blocks dispatch
    back to back (jax's async dispatch pipelines them); ``sync``
    waits for each.  Returns ``(ReplayResult, metrics)``."""
    from repro.core.des import ReplayResult
    from repro.core.engine import pool_size

    P = pool_size(pool)
    plan = engine.plan(pool)           # P-periodic: one chunk per shard
    plan = plan * (B // mesh.shape[axis]) if plan is not None else None
    res_blocks, met_blocks = [], []
    for inputs, rows in blocks:
        res, metrics = _replay_block_sharded(engine, mesh, axis, plan,
                                             *inputs)
        if sync:
            jax.block_until_ready((res, metrics))
        if rows != B:                  # only a partial block pays a trim
            trim = lambda x: x[:rows * P]
            res = res._replace(state=jax.tree.map(trim, res.state),
                               events=trim(res.events),
                               deadlocked=trim(res.deadlocked))
            metrics = jax.tree.map(trim, metrics)
        res_blocks.append(res)
        met_blocks.append(metrics)
    if not res_blocks:
        raise ValueError("no scenario blocks")
    cat = (lambda *xs: xs[0] if len(xs) == 1
           else jnp.concatenate(xs, axis=0))
    res = ReplayResult(
        state=jax.tree.map(cat, *[r.state for r in res_blocks]),
        events=cat(*[r.events for r in res_blocks]),
        iters=sum(r.iters.sum() for r in res_blocks),
        deadlocked=cat(*[r.deadlocked for r in res_blocks]),
        pass_invocations=sum(r.pass_invocations.sum()
                             for r in res_blocks))
    return res, jax.tree.map(cat, *met_blocks)


@functools.partial(jax.jit,
                   static_argnames=("spec", "P", "B", "S", "lo", "width"))
def _window_block_inputs(submit, nodes, est, true_rt, valid, totals, pool,
                         spec, P, B, S, lo, width, blo):
    """One fixed-shape block of a fan window, expanded ON DEVICE from
    the one uploaded base: window rows ``r = blo .. blo+B`` of the
    ``S·width`` rectangle (``r = s·width + w`` ⇒ member ``φ = lo + w``;
    ids past S·width are inert padding), assembled into donatable
    (B·P)-fork replay inputs.  ``lo``/``width`` are static (a rung
    schedule is fixed, so each rung compiles once); ``blo`` is a
    dynamic operand, so every block of a window shares one compile."""
    from repro.core.engine import _assemble_replay_inputs
    from repro.core.fan import perturb_window
    r = blo + jnp.arange(B)
    rows = perturb_window(submit, nodes, est, true_rt, valid, totals,
                          spec, r, lo, width, S)
    return _assemble_replay_inputs(*rows, pool, P)


def _stream_window(engine: DrainEngine, mesh: Mesh, axis: str, scenarios,
                   pool: EnginePool, spec, lo: int, width: int,
                   block_size: Optional[int]):
    """Members ``φ ∈ [lo, lo+width)`` of the fan over ``pool``, streamed:
    the ``S·width`` window rows in blocks of ``block_size`` (rounded up
    to the axis size), each expanded on device from the scenarios'
    device arrays, uploaded once (H2D stays O(1) in F).  The whole fan
    is the window ``[0, F)``."""
    from repro.core.engine import _scenario_arrays, pool_size
    base = _scenario_arrays(scenarios)
    S = int(scenarios.total_nodes.shape[0])
    R = S * width
    B = _round_up(block_size or R, mesh.shape[axis])
    P = pool_size(pool)
    blocks = ((_window_block_inputs(*base, pool, spec, P, B, S, lo, width,
                                    jnp.int32(blo)), min(B, R - blo))
              for blo in range(0, R, B))
    return _stream_blocks(engine, mesh, axis, pool, B, blocks)


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
                yield pad_scenarios(blk, B), n

        stream = ingest()
        if prefetch_depth > 0:
            stream = prefetch(stream, depth=prefetch_depth)
        res, metrics = _stream_blocks(
            eng, mesh, axis, pool, B,
            ((replay_inputs(padded, pool), n) for padded, n in stream),
            sync=prefetch_depth <= 0)
        costs, best = grid_select_jit(goal, metrics, res.deadlocked, Psz)
        S_out = res.deadlocked.shape[0] // Psz
        return _shape_outcome(res, metrics, (S_out, Psz), costs, best)

    return wrapper


def sharded_fan_grid(mesh: Mesh, axis: str = "data",
                     engine: Optional[DrainEngine] = None,
                     objective: ObjectiveLike = None, *,
                     fan=None,
                     block_size: Optional[int] = None):
    """Fleet-scale Monte-Carlo fans (DESIGN.md §§9–10): the
    ``engine.fan_grid`` pseudo-scenario axis (``g = s·F + φ``, G = S·F
    rows) sharded over ``axis`` of ``mesh`` and streamed in fixed-size
    blocks, exactly like ``sharded_replay_grid`` streams scenarios.

    The fan is one full-pool member window ``[0, F)`` through the one
    block streamer (``_stream_window``), then ``fan_select_jit``: fan
    members ARE pseudo-scenarios, so hoist plans stay P-periodic and
    padding rows are inert.  There is NO host ingest thread to
    overlap — each block is expanded on device from the one uploaded
    base (H2D stays O(1) in F), so blocks dispatch back-to-back and
    jax's async dispatch pipelines them.  Fan member draws are keyed
    per (s, φ) independently of the block cut, so any ``block_size``
    is bit-identical to the one-shot ``fan_grid``.

    ``fan`` is a ``FanSpec`` (or bare int F); ``block_size`` counts
    pseudo-scenarios per device step (i.e. ``block_size // F`` base
    scenarios), rounded up to the axis size.  Returns a function
    ``(scenarios, pool) -> FanOutcome``.
    """
    from repro.core.engine import (_fan_outcome, as_pool, fan_select_jit,
                                   pool_size)
    from repro.core.fan import normalize_fan

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective)
    spec = normalize_fan(fan if fan is not None else 1)

    def wrapper(scenarios, pool: PoolArg):
        pool = as_pool(_engine_pool(pool))
        Psz = pool_size(pool)
        S = int(scenarios.total_nodes.shape[0])
        res, metrics = _stream_window(eng, mesh, axis, scenarios, pool,
                                      spec, 0, spec.n, block_size)
        return _fan_outcome(res, metrics, (S, spec.n, Psz), fan_select_jit(
            goal, metrics, res.deadlocked, spec.n, Psz))

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


def sharded_race_grid(mesh: Mesh, axis: str = "data",
                      engine: Optional[DrainEngine] = None,
                      objective: ObjectiveLike = None, *,
                      race=None,
                      block_size: Optional[int] = None):
    """Fleet-scale adaptive racing (DESIGN.md §§9–11): each rung of the
    successive-halving race streams its ``S·width`` member-window rows
    through the one block streamer (``_stream_window`` — window rows
    are pseudo-scenarios like any other), and
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
    from repro.core.engine import as_pool, fan_select_jit, pool_size
    from repro.core.race import normalize_race, run_race, sub_pools

    eng = engine or DEFAULT_ENGINE
    goal = resolve_goal(objective)
    spec = normalize_race(race if race is not None else 1)

    def wrapper(scenarios, pool: PoolArg):
        pool_full = as_pool(_engine_pool(pool))
        Psz = pool_size(pool_full)
        S = int(scenarios.total_nodes.shape[0])
        sub_pool = sub_pools(pool_full)
        passes = [0]

        def eval_window(active, lo, hi):
            sub = sub_pool(active)
            res, metrics = _stream_window(eng, mesh, axis, scenarios, sub,
                                          spec.fan, lo, hi - lo,
                                          block_size)
            passes[0] += int(res.pass_invocations)
            return fan_select_jit(goal, metrics, res.deadlocked, hi - lo,
                                  pool_size(sub))[0]

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
