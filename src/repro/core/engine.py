"""Policy-batched drain engine with pluggable scheduling-pass backends.

This is the hot spot of the whole system (DESIGN.md §1): every decision
cycle forks the synchronized snapshot into k what-if simulations — one
per candidate policy (times ``n_ens`` ensemble members) — and drains
each to completion.  Instead of ``jax.vmap`` over a scalar DES, the
``DrainEngine`` carries all forks as an explicit leading batch axis on
``SimState`` and advances them in lock-step with ONE ``lax.while_loop``
(``repro.core.des.simulate_to_drain_batched``).  Per event:

  1. priority keys are computed and argsorted once for the WHOLE batch
     (one (k, J) argsort, not k separate sorts inside each fork) — the
     pool is a parametric ``policies.PolicySpec`` PyTree (family (k,),
     θ (k, P)), so DRAS-style parameter sweeps and learned scorers are
     just more rows on the fork axis; legacy i32 id pools still work
     through the same entry points (the bit-exact oracle path);
  2. the inherently sequential greedy + EASY-backfill pass runs through
     a registered *backend* on the batch axis;
  3. starts are applied and every fork advances to its own next
     predicted completion, with per-fork done/dead masks.

Backends (registered in ``PASS_BACKENDS``):

  * ``reference`` — today's pure-JAX ``schedule_pass`` logic
    (``backfill.schedule_pass_with_order``) vmapped over the fork axis.
    The semantic oracle: bit-identical to the scalar DES.
  * ``pallas``    — ``kernels.policy_eval.policy_eval_pass_batched``,
    the TPU kernel with the fork axis on the grid and the queue in
    VMEM.  Compiled on a TPU, interpret mode elsewhere (the CPU test
    runs); ``interpret=None`` resolves from the platform.

Every decision is one program (DESIGN.md §1): ``_members`` broadcasts
the snapshot to ``W·k`` forks (fork ``f = w·k + p``, member w's
estimates and capacity from a member source, member 0 exact), drains
them once (here, or per shard under a ``(mesh, axis)`` placement), and
``_decision`` reduces the members to a packed ``Decision``.  The
sources: none (``decide``, the nominal program the twin runs), a fan
window (``decide_fan`` over ``[0, F)``, ``race.decide_race``'s rungs
over later windows) and the ensemble's estimate noise
(``decide_ensemble``); ``whatif.sharded_whatif`` is the nominal decide
with a placement.  ``SchedTwin`` gets its engine at construction, and
the cluster emulator's static mode runs a k=1 engine, so baselines
stay bit-identical to the twin's simulator.

The engine also hosts the **scenario-vectorized replay** (DESIGN.md
§6): ``replay`` / ``replay_grid`` drive ``des.simulate_replay_batched``
over a ``workload.ScenarioSet``, stacking an S-scenario axis on top of
the P-policy fork axis (flat fork f = s·P + p) — a whole baseline grid
in one device computation, bit-identical to the host emulator's event
loop, sharded by scenario via ``whatif.sharded_replay_grid``.  A
Monte-Carlo fan (§10) is the member window ``[0, F)`` of
``fan_window_grid``.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import warnings
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import scoring
from repro.core.backfill import (priority_order,
                                 schedule_pass_with_order,
                                 static_priority_order)
from repro.core.fan import (FanSpec, _member_draws, failure_downs,
                            lognormal_noise, normalize_fan, perturb_window)
from repro.core.objective import (DEFAULT_OBJECTIVE, Objective,
                                  ObjectiveLike, as_distributional,
                                  resolve_goal)
from repro.core.des import (DrainMetrics, DrainResult, ReplayResult,
                            broadcast_state, drain_metrics,
                            simulate_replay_batched,
                            simulate_to_drain_batched, state_metrics)
from repro.core.policies import PolicySpec, time_invariant_mask
from repro.core.state import (QUEUED, RUNNING, TIME_NONE, JobTable,
                              SimState)
from repro.kernels import policy_eval as _pe
from repro.launch.mesh import shard_map

logger = logging.getLogger(__name__)


def _quiet_donation(jitted):
    """Buffer donation on ``_drain``/``_replay`` lets XLA update the
    (k, J) while-loop carries in place; backends without donation
    support (CPU) warn per compile.  Suppress exactly that warning,
    exactly around this engine's donated calls — never globally."""
    @functools.wraps(jitted)
    def call(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted(*args, **kwargs)
    call.lower = jitted.lower
    return call

#: What the engine accepts as a pool: a parametric ``PolicySpec`` with
#: a leading fork axis (the post-tentpole representation) or a legacy
#: i32 id vector (kept as the bit-exact pre-parametric oracle path).
EnginePool = Union[PolicySpec, jax.Array]


def pool_size(pool: EnginePool) -> int:
    """Number of forks k in a pool of either representation."""
    if isinstance(pool, PolicySpec):
        return pool.family.shape[0]
    return pool.shape[0]


def tile_pool(pool: EnginePool, n: int) -> EnginePool:
    """Repeat a pool n times along the fork axis (ensemble stacking /
    one pool copy per replay scenario)."""
    if isinstance(pool, PolicySpec):
        return PolicySpec(jnp.tile(pool.family, n),
                          jnp.tile(pool.theta, (n, 1)))
    return jnp.tile(pool, n)


def as_pool(policy) -> EnginePool:
    """Lift a single policy — a ``PolicySpec`` fork or a legacy integer
    id — into a k=1 pool (pools pass through unchanged)."""
    if isinstance(policy, PolicySpec):
        if policy.family.ndim == 0:
            return PolicySpec(policy.family.reshape(1),
                              policy.theta.reshape(1, -1))
        return policy
    arr = jnp.asarray(policy, jnp.int32)
    return arr.reshape(1) if arr.ndim == 0 else arr


class _DecisionFields(NamedTuple):
    policy_index: jax.Array   # index into the pool (NOT the policy id)
    costs: jax.Array          # (k,) per-policy objective cost
    run_mask: jax.Array       # bool (max_jobs,) jobs to start now (qrun set)
    metrics: DrainMetrics     # (k,)-leading metrics for telemetry
    deadlocked: jax.Array     # (k,) bool
    cost_terms: Optional[Dict[str, jax.Array]] = None  # term -> (k,)
    cost_ci: Optional[jax.Array] = None    # (k,) 95% CI half-width
    fan_width: Optional[jax.Array] = None  # (k,) member-cost spread
    fan_size: int = 1                      # members behind the costs
    words: Optional[jax.Array] = None      # int32 host view (``packed``)


#: The fields ``Decision.words`` holds.
_WORD_FIELDS = frozenset({"run_mask", "policy_index", "costs", "cost_terms",
                          "cost_ci", "fan_width"})


class HostDecision(NamedTuple):
    """A decision's results on the host, read from its ``words``."""
    run_mask: np.ndarray                  # bool (J,)
    policy_index: int
    costs: np.ndarray                     # float32 (k,)
    cost_terms: Dict[str, np.ndarray]     # term -> float32 (k,)
    cost_ci: Optional[np.ndarray]
    fan_width: Optional[np.ndarray]


class Decision(_DecisionFields):
    """One scheduling cycle's outcome (re-exported by ``whatif``).

    ``costs`` is the goal's compiled cost per fork (argmin = winner);
    ``cost_terms`` the goal's per-term breakdown for ALL k forks
    (``Objective.cost_terms`` — telemetry records every fork's
    decomposition, not just the winning index).

    Fan/ensemble decisions (``decide_fan`` / ``decide_ensemble``) also
    stamp per-policy uncertainty, computed on DEVICE from the member
    costs (no host recompute): ``cost_ci`` is the 95% normal CI
    half-width of the member-cost mean (``1.96·σ/√F``; +inf when any
    member deadlocked), ``fan_width`` the full member-cost spread
    (worst − best member; the "how sure is the twin" headline), and
    ``fan_size`` the member count F.  Single-future decisions leave
    them None/1.

    ``words`` is what the host reads of a decision, in ONE transfer: a
    flat int32 buffer built in the producing program (``packed``) —
    the run mask as 0/1 (J,), the winner's index, then the float32
    rows ``costs``, each ``cost_terms`` value in sorted key order (a
    jitted program's dict outputs come back sorted), ``cost_ci``
    and ``fan_width`` where present, each (k,) and bitcast, so every
    value (an inf of a deadlocked fork too) crosses bit for bit.
    ``unpack`` reads it back from the shapes and keys the decision
    already has on the host; ``_replace`` of a field it holds packs it
    anew, so the words never disagree with the fields."""
    __slots__ = ()

    def packed(self) -> "Decision":
        """This decision with its ``words``."""
        terms = self.cost_terms or {}
        rows = [self.costs, *(terms[t] for t in sorted(terms)),
                *(x for x in (self.cost_ci, self.fan_width)
                  if x is not None)]
        floats = jnp.stack(rows).astype(jnp.float32)
        words = jnp.concatenate([
            jnp.asarray(self.run_mask).astype(jnp.int32),
            jnp.reshape(jnp.asarray(self.policy_index, jnp.int32), (1,)),
            jax.lax.bitcast_convert_type(floats, jnp.int32).reshape(-1)])
        return super()._replace(words=words)

    def unpack(self, words: np.ndarray) -> HostDecision:
        """``words``, read to the host, as its fields."""
        J = self.run_mask.shape[-1]
        k = self.costs.shape[-1]
        rows = words[J + 1:].view(np.float32).reshape(-1, k)
        terms = list(self.cost_terms or {})
        by_name = dict(zip(sorted(terms), rows[1:]))
        rest = iter(rows[1 + len(terms):])
        return HostDecision(
            run_mask=words[:J] != 0,
            policy_index=int(words[J]),
            costs=rows[0],
            cost_terms={t: by_name[t] for t in terms},
            cost_ci=next(rest) if self.cost_ci is not None else None,
            fan_width=next(rest) if self.fan_width is not None else None)

    def _replace(self, **changes) -> "Decision":
        out = super()._replace(**changes)
        if (self.words is not None and "words" not in changes
                and not _WORD_FIELDS.isdisjoint(changes)):
            return out.packed()
        return out


class ReplayOutcome(NamedTuple):
    """A replayed (scenario × policy) grid (DESIGN.md §6).

    Leading axes are (S, P) from ``replay_grid`` — flat fork f = s·P + p
    — and (P,) from ``replay`` (S squeezed).  ``start_t``/``end_t`` are
    ACTUAL times (completions retire at ground-truth ends); ``metrics``
    score true outcomes (runtime = ground truth) over each scenario's
    real slots, per-scenario ``total_nodes`` included.

    ``costs``/``best`` are the per-objective selection (DESIGN.md §8):
    the goal's compiled cost over the policy axis ((S, P) / (P,),
    deadlocked forks at +inf) and its per-scenario argmin ((S,) /
    scalar) — the policy the twin would pick for each replayed future.
    """
    start_t: jax.Array        # f32 (..., J)
    end_t: jax.Array          # f32 (..., J)
    metrics: DrainMetrics     # (...)-leading
    deadlocked: jax.Array     # bool (...)
    events: jax.Array        # i32 (...) — events processed per fork
    result: ReplayResult      # the raw flat (k = S·P) replay result
    costs: Optional[jax.Array] = None   # objective costs (..., P)-shaped
    best: Optional[jax.Array] = None    # per-scenario winning pool index


class FanOutcome(NamedTuple):
    """A (scenario × fan member × policy) Monte-Carlo grid
    (DESIGN.md §10) from ``DrainEngine.fan_grid``.

    Leading axes are (S, F, P) — flat fork ``f = (s·F + φ)·P + p`` —
    with member φ=0 the unperturbed base future.  ``member_costs`` is
    the inner goal's cost per member (deadlocked members at +inf);
    ``costs`` the distributional reduction over the fan axis (what the
    argmin ``best`` selects per scenario); ``cost_ci``/``fan_width``
    the per-(s, p) uncertainty stamps (``member_uncertainty``)."""
    start_t: jax.Array        # f32 (S, F, P, J) — actual start times
    end_t: jax.Array          # f32 (S, F, P, J)
    metrics: DrainMetrics     # (S, F, P)-leading
    deadlocked: jax.Array     # bool (S, F, P)
    events: jax.Array         # i32 (S, F, P)
    result: Optional[ReplayResult]  # raw flat (k = S·F·P) replay result;
                              # None when the outcome was ASSEMBLED from
                              # donated pieces (pruned/raced grids)
    member_costs: jax.Array   # (S, F, P) inner costs per member
    costs: jax.Array          # (S, P) reduced distributional costs
    best: jax.Array           # (S,) per-scenario winning pool index
    cost_ci: jax.Array        # (S, P) 95% CI half-width of member mean
    fan_width: jax.Array      # (S, P) worst − best member cost


# ----------------------------------------------------------------------
# Pass backends: (batched SimState, order (k, J), rank limit (i32
# scalar | None)) -> started (k, J) bool
# ----------------------------------------------------------------------

PassFn = Callable[[SimState, jax.Array, object], jax.Array]
PASS_BACKENDS: Dict[str, Callable[["DrainEngine"], PassFn]] = {}


def register_backend(name: str):
    """Register a pass-backend factory under ``name`` (the value of the
    ``backend`` knob on ``configs.schedtwin.TwinConfig``)."""
    def deco(factory: Callable[["DrainEngine"], PassFn]):
        PASS_BACKENDS[name] = factory
        return factory
    return deco


@register_backend("reference")
def _reference_backend(engine: "DrainEngine") -> PassFn:
    """The pure-JAX oracle pass, vmapped over the fork axis (the rank
    limit is a lock-step scalar shared by every fork, so it maps with
    ``in_axes=None``)."""
    def pass_fn(states: SimState, order: jax.Array, limit) -> jax.Array:
        res = jax.vmap(schedule_pass_with_order,
                       in_axes=(0, 0, None))(states, order, limit)
        return res.started
    return pass_fn


@register_backend("pallas")
def _pallas_backend(engine: "DrainEngine") -> PassFn:
    interpret = engine.resolved_interpret()

    def pass_fn(states: SimState, order: jax.Array, limit) -> jax.Array:
        jobs = states.jobs
        running = jobs.state == RUNNING
        started, _ = _pe.policy_eval_pass_batched(
            order,
            jobs.state == QUEUED,
            jobs.nodes,
            jobs.est_runtime,
            jnp.where(running, jobs.end_t, jnp.inf),
            jnp.where(running, jobs.nodes, 0),
            states.free_nodes,
            states.now,
            limit,
            interpret=interpret)
        return started > 0
    return pass_fn


def batched_priority_order(states: SimState, pool: EnginePool) -> jax.Array:
    """(k, J) priority order for the whole fork batch: one batched key
    evaluation + ONE argsort per event (stable; ties -> slot order).
    Single-sourced from ``backfill.priority_order`` so the engine can
    never drift from the scalar oracle's tie-break semantics.

    ``pool`` is a ``PolicySpec`` PyTree (family (k,), theta (k, P)) or
    a legacy (k,) id vector; either way the fork axis is the leading
    axis vmap maps over.  θ stays in this stage — outside the pass
    kernel — so backends are untouched by pool parameterization."""
    return jax.vmap(priority_order)(states, pool)


# ----------------------------------------------------------------------
# Static-key hoisting (DESIGN.md §7): forks whose keys never depend on
# the clock get their argsort computed ONCE, outside the event loop.
# ----------------------------------------------------------------------

#: A hoist plan: per-fork "keys are time-invariant" bools, decided on
#: the HOST (``policies.time_invariant_mask`` over the concrete pool)
#: and passed as a *static* jit argument — the fork-axis split must be
#: known at trace time for the gather/sort/scatter below to have static
#: shapes.  ``None`` disables hoisting (every fork re-sorts per event).
HoistPlan = Optional[Tuple[bool, ...]]


def hoist_plan(pool: EnginePool, enabled: bool = True) -> HoistPlan:
    """Derive the static hoist plan from a CONCRETE pool.  Returns None
    when hoisting is disabled, no fork qualifies, or the pool is a
    tracer (e.g. inside a caller's jit / under sharding constraints) —
    the engine then falls back to per-event sorting for all forks."""
    if not enabled:
        return None
    leaves = jax.tree.leaves(pool)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return None
    mask = time_invariant_mask(pool)
    if not mask.any():
        return None
    return tuple(bool(b) for b in mask)


def shard_local_plan(plan: HoistPlan, n_shards: int) -> HoistPlan:
    """Repartition a full-pool hoist plan for a ``shard_map`` body that
    sees only its shard's block of the fork axis (DESIGN.md §9).

    ``shard_map`` traces ONE program executed by every device, so a
    static per-shard plan is only expressible when every shard's chunk
    of the full plan is IDENTICAL — then the common chunk simply *is*
    the local plan, and each device hoists its own forks' argsorts with
    zero cross-shard traffic (this is what re-enables the PR-4
    compaction win under sharding; the replay grid's plan is periodic
    in P, so its chunks always agree).  Heterogeneous chunks (or a fork
    count that doesn't block-split) fall back to ``None`` — per-event
    sorting for all forks, bit-identical either way."""
    if plan is None or n_shards <= 1:
        return plan
    k = len(plan)
    if k % n_shards:
        return None
    chunk = k // n_shards
    first = plan[:chunk]
    for i in range(1, n_shards):
        if plan[i * chunk:(i + 1) * chunk] != first:
            return None
    return first if any(first) else None


def _index_pool(pool: EnginePool, idx: jax.Array) -> EnginePool:
    if isinstance(pool, PolicySpec):
        return PolicySpec(pool.family[idx], pool.theta[idx])
    return pool[idx]


def _compact_queued_first(order: jax.Array, queued: jax.Array) -> jax.Array:
    """Stable-partition each fork's rank order so QUEUED slots occupy
    the leading ranks — one cumsum + row scatter, O(k·J), no sort.

    The relative order of queued ranks is preserved, so the pass visits
    the exact same queued sequence (non-queued ranks are no-ops either
    way) — bit-exact — while restoring ``des.pass_rank_limit``'s
    queued-first contract for hoisted static orders, whose queued slots
    would otherwise sit scattered at arbitrary rank depths and pin the
    dynamic bound near J."""
    q = jnp.take_along_axis(queued, order, axis=1)          # (k, J)
    nq = jnp.cumsum(q, axis=1)
    pos = jnp.where(q, nq - 1, nq[:, -1:] + jnp.cumsum(~q, axis=1) - 1)
    k = order.shape[0]
    return jnp.zeros_like(order).at[jnp.arange(k)[:, None], pos].set(order)


def hoisted_orders(states0: SimState, pool: EnginePool, plan: HoistPlan,
                   ever_queued: jax.Array) -> jax.Array:
    """The (n_ti, J) static priority orders of ``plan``'s
    time-invariant forks — the argsorts ``make_order_fn`` hoists out of
    the event loop.  Split out so fleet callers can compute it OUTSIDE
    a ``shard_map`` body and feed it back in as a sharded argument:
    jax 0.4 miscompiles an argsort that is loop-invariant to a
    ``while_loop`` consuming it via gathers inside ``shard_map``
    (non-leading shards read corrupted orders); a sort performed in the
    surrounding GSPMD region and passed through the shard boundary as
    an input is partitioned correctly (tests/test_fleet.py pins the
    parity)."""
    plan_arr = np.asarray(plan, dtype=bool)
    ti_idx = jnp.asarray(np.nonzero(plan_arr)[0], dtype=jnp.int32)
    states_ti = jax.tree.map(lambda x: x[ti_idx], states0)
    with jax.named_scope("keys"):
        return jax.vmap(static_priority_order)(
            states_ti, _index_pool(pool, ti_idx), ever_queued[ti_idx])


def on_shards(body: Callable, mesh, axis: str, plan: HoistPlan,
              ever_queued: jax.Array, states: SimState, pool: EnginePool,
              *operands):
    """``body(states, pool, *operands, hoisted=None)`` run per shard of
    the fork axis under ``shard_map`` (DESIGN.md §9): every argument
    splits on its leading axis over ``axis`` of ``mesh``, and ``plan``
    is the shard-local hoist plan.  The hoisted static argsorts are
    computed HERE, outside the body, and cross the shard boundary as a
    sharded input (see ``hoisted_orders``): ``plan`` tiled once per
    shard is the global plan, so each device gets its own rows."""
    args = (states, pool, *operands)
    if plan is not None:
        args += (hoisted_orders(states, pool, plan * mesh.shape[axis],
                                ever_queued),)
    spec = PartitionSpec(axis)
    return shard_map(body, mesh, in_specs=(spec,) * len(args),
                     out_specs=spec)(*args)


def make_order_fn(states0: SimState, pool: EnginePool, plan: HoistPlan,
                  ever_queued: jax.Array,
                  hoisted: Optional[jax.Array] = None,
                  ) -> Callable[[SimState], jax.Array]:
    """The per-event order stage, with static-key forks hoisted.

    ``ever_queued`` (k, J) marks every slot that can EVER be queued
    during this drain/replay (drain: currently queued; replay: slots
    with a finite arrival).  Time-invariant forks (per ``plan``) rank
    those slots once via ``backfill.static_priority_order`` — exact
    because their keys never change and the pass skips non-QUEUED
    ranks — so each event's (k, J) sort shrinks to the time-varying
    rows only (or disappears entirely for an all-static pool).  The
    hoisted rows are re-compacted queued-first per event (a cumsum, not
    a sort) to keep the dynamic pass bound tight.

    ``hoisted`` optionally supplies the precomputed static orders
    (``hoisted_orders``) — the shard-local fleet paths pass their
    shard's rows in to keep the argsort outside the ``shard_map`` body
    (see ``hoisted_orders`` for why).
    """
    if plan is None:
        return lambda st: batched_priority_order(st, pool)
    plan_arr = np.asarray(plan, dtype=bool)
    ti_idx = jnp.asarray(np.nonzero(plan_arr)[0], dtype=jnp.int32)
    if hoisted is None:
        hoisted = hoisted_orders(states0, pool, plan, ever_queued)

    if plan_arr.all():
        # zero per-event sorting: just repartition the fixed ranking
        def order_fn_all(st: SimState) -> jax.Array:
            return _compact_queued_first(hoisted, st.jobs.state == QUEUED)
        return order_fn_all

    tv_idx = jnp.asarray(np.nonzero(~plan_arr)[0], dtype=jnp.int32)
    pool_tv = _index_pool(pool, tv_idx)
    # merge hoisted + fresh rows with ONE static gather (a concat and
    # an inverse permutation) instead of two row scatters
    perm = np.concatenate([np.nonzero(plan_arr)[0], np.nonzero(~plan_arr)[0]])
    inv = jnp.asarray(np.argsort(perm), dtype=jnp.int32)

    def order_fn(st: SimState) -> jax.Array:
        compacted = _compact_queued_first(
            hoisted, (st.jobs.state == QUEUED)[ti_idx])
        st_tv = jax.tree.map(lambda x: x[tv_idx], st)
        fresh = batched_priority_order(st_tv, pool_tv)
        return jnp.concatenate([compacted, fresh], axis=0)[inv]
    return order_fn


# ----------------------------------------------------------------------
# The engine.
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DrainEngine:
    """Pluggable, policy-batched what-if engine.

    Frozen + hashable so an engine instance is a static jit argument:
    each (backend, interpret) pair compiles once and is cached.

    Parameters
    ----------
    backend : name in ``PASS_BACKENDS`` ("reference" | "pallas"), or
        "auto" — resolved at construction to "pallas" on TPU and
        "reference" on CPU/GPU (interpret-mode pallas is ~2.3x slower
        than reference at k=32 on CPU, see BENCH_overhead.json; the
        kernel only pays off compiled).  The resolved choice is logged.
    interpret : Pallas interpret-mode override.  ``None`` resolves from
        the platform (``policy_eval.default_interpret``): compiled on
        TPU, interpreted on CPU.
    dynamic_bounds : truncate the pass's sequential rank loops at the
        deepest live queued rank each event (``des.pass_rank_limit``) —
        bit-exact; collapses the O(J)-rank loops to the queue depth.
    hoist_static : hoist the argsort of time-invariant forks
        (``policies.time_invariant_mask``) out of the event loop.
    elide_empty : skip keys + argsort + pass entirely on replay
        iterations where no live fork has a queued job.

    The three compaction knobs (DESIGN.md §7) exist for ablation
    benchmarks and bit-identity tests against the uncompacted engine;
    production code leaves them on.
    """

    backend: str = "reference"
    interpret: Optional[bool] = None
    dynamic_bounds: bool = True
    hoist_static: bool = True
    elide_empty: bool = True

    def __post_init__(self) -> None:
        if self.backend == "auto":
            platform = jax.default_backend()
            resolved = "pallas" if platform == "tpu" else "reference"
            logger.info("DrainEngine backend='auto' resolved to %r "
                        "(jax platform: %s)", resolved, platform)
            object.__setattr__(self, "backend", resolved)
        if self.backend not in PASS_BACKENDS:
            raise ValueError(
                f"unknown pass backend {self.backend!r}; "
                f"registered: {sorted(PASS_BACKENDS)}")

    def resolved_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return _pe.default_interpret()

    def pass_fn(self) -> PassFn:
        return PASS_BACKENDS[self.backend](self)

    def plan(self, pool: EnginePool) -> HoistPlan:
        """The static hoist plan this engine uses for ``pool`` (None
        when ``hoist_static`` is off or no fork qualifies)."""
        return hoist_plan(pool, enabled=self.hoist_static)

    # -- drains --------------------------------------------------------
    def drain_batched(self, states: SimState, pool: EnginePool) -> DrainResult:
        """Drain pre-batched fork states (leading axis == pool).

        ``states`` buffers are DONATED to the computation (in-place
        carry updates on backends that support it) — don't reuse them
        after the call."""
        return _drain(self, states, pool, self.plan(pool))

    def drain(self, state: SimState, pool: EnginePool) -> DrainResult:
        """Fork one snapshot across the pool and drain all forks."""
        return _drain(self, broadcast_state(state, pool_size(pool)),
                      pool, self.plan(pool))

    # -- decision cycles ----------------------------------------------
    def decide(self, state: SimState, pool: EnginePool,
               objective: ObjectiveLike = None, *,
               weights: Optional[scoring.ScoreWeights] = None) -> Decision:
        """One decision cycle under ``objective`` (an ``Objective``, a
        grammar string, or None for the paper score).  ``weights=`` is
        the deprecated legacy spelling (lifted, bit-identical)."""
        goal = resolve_goal(objective, weights)
        return _decide(self, state, pool, goal, self.plan(pool))

    def decide_ensemble(self, state: SimState, pool: EnginePool,
                        key: jax.Array, n_ens: int = 8, noise: float = 0.3,
                        objective: ObjectiveLike = None, *,
                        weights: Optional[scoring.ScoreWeights] = None,
                        ) -> Decision:
        goal = resolve_goal(objective, weights)
        d = _decide_ensemble(self, state, pool, key, n_ens, noise,
                             goal, self.plan(pool))
        return d._replace(fan_size=n_ens)

    def decide_fan(self, state: SimState, pool: EnginePool, fan,
                   objective: ObjectiveLike = None, *,
                   weights: Optional[scoring.ScoreWeights] = None
                   ) -> Decision:
        """One decision cycle over a Monte-Carlo fan of F perturbed
        futures per policy (DESIGN.md §10): fork ``f = φ·k + p`` drains
        policy p under member φ's estimate-noise and node-failure draws
        (member 0 exact; arrival-burst warps are a replay concern — a
        drain has no future arrivals).  ``objective`` may be
        distributional (``"p95:avg_wait"``, ``"cvar:0.9:score"``, ...);
        plain goals reduce by the member mean.  The returned
        ``Decision`` carries ``cost_ci``/``fan_width``/``fan_size``.
        ``fan`` is a ``FanSpec`` or a bare int F."""
        goal = resolve_goal(objective, weights)
        spec = normalize_fan(fan)
        d = _decide_fan(self, state, pool, spec, goal, self.plan(pool))
        return d._replace(fan_size=spec.n)

    # -- single pass (k=1) — the emulator's static baseline mode -------
    def schedule_pass_starts(self, state: SimState, policy) -> jax.Array:
        """Started mask (J,) for ONE policy (``PolicySpec`` fork or
        legacy integer id) on an unbatched state."""
        return _single_pass(self, state, as_pool(policy))

    # -- trace replay (DESIGN.md §6) -----------------------------------
    def replay(self, scenario, pool, objective: ObjectiveLike = None, *,
               weights: Optional[scoring.ScoreWeights] = None
               ) -> ReplayOutcome:
        """Replay ONE scenario (an S=1 ``workload.ScenarioSet``) under
        every fork of ``pool`` — (P,)-leading outcome.  Bit-identical
        to P host-emulator static-mode runs (tests/test_replay.py).
        ``objective`` drives the outcome's ``costs``/``best``
        selection (the trace times themselves are goal-independent)."""
        S = int(scenario.total_nodes.shape[0])
        if S != 1:
            raise ValueError(
                f"replay takes one scenario (got {S}); use replay_grid")
        args = self._grid_args(scenario, pool, objective, weights)
        res, metrics, costs, best = _replay(*args)
        return _shape_outcome(res, metrics, (args[-1],), costs, best)

    def replay_grid(self, scenarios, pool, objective: ObjectiveLike = None,
                    *, weights: Optional[scoring.ScoreWeights] = None
                    ) -> ReplayOutcome:
        """Evaluate the full (scenario × policy) grid — S·P forks, ONE
        device computation.  Fork f = s·P + p; outcome axes (S, P).
        ``objective`` selects per scenario: ``best[s]`` is the pool
        index the goal picks for scenario s (costs over the P axis)."""
        args = self._grid_args(scenarios, pool, objective, weights)
        S, P = int(scenarios.total_nodes.shape[0]), args[-1]
        res, metrics, costs, best = _replay(*args)
        return _shape_outcome(res, metrics, (S, P), costs, best)

    def lower_replay_grid(self, scenarios, pool,
                          objective: ObjectiveLike = None
                          ) -> jax.stages.Lowered:
        """The jitted computation ``replay_grid`` runs, lowered for the
        default device: ``.compile().as_text()`` shows what the
        compiler made of it (e.g. a ``tpu_custom_call`` for the
        compiled Pallas pass)."""
        return _replay.lower(*self._grid_args(scenarios, pool, objective))

    def _grid_args(self, scenarios, pool, objective, weights=None):
        """``_replay``'s arguments for the S×P grid (fork f = s·P + p)."""
        goal = resolve_goal(objective, weights)
        pool = as_pool(pool)
        S = int(scenarios.total_nodes.shape[0])
        P = pool_size(pool)
        plan = self.plan(pool)
        return (self, *replay_inputs(scenarios, pool),
                plan * S if plan is not None else None, goal, P)

    def fan_grid(self, scenarios, pool, fan,
                 objective: ObjectiveLike = None, *,
                 weights: Optional[scoring.ScoreWeights] = None
                 ) -> FanOutcome:
        """The Monte-Carlo fan grid (DESIGN.md §10): every (scenario,
        policy) cell of ``replay_grid`` evaluated under F perturbed
        futures — S·F·P forks, ONE device computation, with the base
        scenarios uploaded once and the perturbations expanded on
        device (fork ``f = (s·F + φ)·P + p``).  ``fan`` is a
        ``FanSpec`` (or a bare int F for a degenerate fan);
        ``objective`` selects per scenario after the distributional
        reduction over the fan axis.  ``FanSpec(n=1)`` (and any
        degenerate spec) is bitwise ``replay_grid``."""
        return self.fan_window_grid(scenarios, pool, fan, objective,
                                    weights=weights)

    def fan_window_grid(self, scenarios, pool, fan,
                        objective: ObjectiveLike = None, *,
                        lo: int = 0, width: Optional[int] = None,
                        weights: Optional[scoring.ScoreWeights] = None
                        ) -> FanOutcome:
        """Replay ONLY members ``φ ∈ [lo, lo+width)`` of the fan — the
        racing/donation suffix.  CRN prefix-stability (``fan.
        perturb_window`` keys on (s, φ) alone) makes every returned
        member bitwise the corresponding member of the full
        ``fan_grid``, so windows replayed at different times
        concatenate into the full fan without ever re-replaying a
        (scenario, policy, member) triple.  The outcome's fan axis has
        ``width`` members and its reduction/selection treats the
        window as the whole fan — racing callers re-reduce over the
        accumulated members instead (``race.rung_stats``)."""
        goal = resolve_goal(objective, weights)
        spec = normalize_fan(fan)
        if width is None:
            width = spec.n - lo
        if not (0 <= lo and lo + width <= spec.n and width >= 1):
            raise ValueError(
                f"member window [{lo}, {lo + width}) outside fan of "
                f"size {spec.n}")
        pool = as_pool(pool)
        S = int(scenarios.total_nodes.shape[0])
        P = pool_size(pool)
        plan = self.plan(pool)              # fork f = (s·width + w)·P + p
        res, metrics, selected = _fan_window_replay(
            self, *_scenario_arrays(scenarios), pool,
            plan * (S * width) if plan is not None else None,
            goal, P, S, spec, lo, width)
        return _fan_outcome(res, metrics, (S, width, P), selected)

    # -- population training (DESIGN.md §13) ---------------------------
    def generation_costs(self, scenarios, pool,
                         objective: ObjectiveLike = None,
                         fan=None) -> jax.Array:
        """The trainer's generation-eval entry point: per-(scenario,
        candidate) costs, (S, P), for a candidate population riding
        the fork axis.  ONE jitted grid — ``replay_grid`` when ``fan``
        is None, else ``fan_grid`` with ``FanSpec``-driven domain
        randomization of the training traces (costs are then the
        goal's distributional reduction over the fan axis).
        Deadlocked rollouts cost +inf, so they rank strictly worst
        under any goal."""
        if fan is None:
            return self.replay_grid(scenarios, pool, objective).costs
        return self.fan_grid(scenarios, pool, fan, objective).costs

    # -- adaptive racing (DESIGN.md §11) -------------------------------
    def race_grid(self, scenarios, pool, race,
                  objective: ObjectiveLike = None):
        """Successive-halving fan evaluation: start every policy at a
        low rung F₀, eliminate CI-dominated policies between rungs,
        replay only the new member suffix for survivors
        (``core/race.py``).  ``race`` is a ``RaceSpec``, a ``FanSpec``
        (raced to ``spec.n`` with default rungs), or a bare int F.
        Returns a ``race.RaceOutcome``."""
        from repro.core.race import race_grid as _race_grid
        return _race_grid(scenarios, pool, race, objective, engine=self)

    def decide_race(self, state: SimState, pool: EnginePool, race,
                    objective: ObjectiveLike = None):
        """One raced decision cycle: the ``decide_fan`` fan grown rung
        by rung with CI elimination and anytime budgets.  Returns
        ``(Decision, race.RaceOutcome)`` — the decision's ``fan_size``
        is the members the winner actually ran, the outcome carries
        the rung accounting (see ``core.race.decide_race``)."""
        from repro.core.race import decide_race as _decide_race
        return _decide_race(state, pool, race, objective, engine=self)


# ----------------------------------------------------------------------
# Jitted implementations (engine static -> cached per configuration).
# ----------------------------------------------------------------------

def _drain_impl(engine: DrainEngine, states: SimState, pool: EnginePool,
                plan: HoistPlan = None,
                hoisted: Optional[jax.Array] = None) -> DrainResult:
    # Mid-drain, no new jobs appear: only slots queued at entry can
    # ever be queued — the tightest hoist domain.
    order_fn = make_order_fn(states, pool, plan,
                             ever_queued=states.jobs.state == QUEUED,
                             hoisted=hoisted)
    return simulate_to_drain_batched(
        states, order_fn, engine.pass_fn(),
        dynamic_bounds=engine.dynamic_bounds)


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("engine", "plan"),
                   donate_argnames=("states",))
def _drain(engine: DrainEngine, states: SimState,
           pool: EnginePool, plan: HoistPlan = None) -> DrainResult:
    return _drain_impl(engine, states, pool, plan)


class Members(NamedTuple):
    """What a decision's forks drained to.  With a member axis (fan
    and ensemble decisions) every field leads with (W, k), fork
    ``f = w·k + p``; the nominal decide has no member axis, (k,)."""
    metrics: DrainMetrics     # per fork, over the snapshot's queued jobs
    deadlocked: jax.Array     # bool
    costs: jax.Array          # the goal's cost per fork, +inf if deadlocked
    first0: jax.Array         # bool (k, J) member 0's first-started rows


def _fan_draws(state: SimState, spec: FanSpec, lo: int, width: int):
    """Members ``φ ∈ [lo, lo+width)`` of a decision's fan: their (W, J)
    estimates and (W,) capacity losses, None where ``spec`` draws
    none.  The draws are the replay fan's (``fan._member_draws`` and
    ``fan.failure_downs`` at s=0: a decision has one base snapshot),
    keyed on (seed, φ) alone, so any window is bitwise those members
    of the whole fan.  ``runtime_noise`` scales the walltime
    ESTIMATES (a drain's predicted ends: what the twin is unsure of);
    arrival warps have no drain-side meaning.  Member 0 is exact."""
    if spec.degenerate:
        return None, None
    cap = state.jobs.capacity
    phi = lo + jnp.arange(width)
    eps, _, u = jax.vmap(
        lambda p: _member_draws(spec.seed, jnp.int32(0), p, cap))(phi)
    est = down = None
    if spec.runtime_noise > 0.0:
        est = lognormal_noise(state.jobs.est_runtime[None, :], eps,
                              spec.runtime_noise, phi == 0)
    if spec.failure_prob > 0.0:
        down = failure_downs(spec, jnp.zeros_like(phi), phi, u,
                             jnp.broadcast_to(state.total_nodes, (width,)))
    return est, down


def _members(engine: DrainEngine, state: SimState, pool: EnginePool,
             objective: Objective, plan: HoistPlan,
             width: Optional[int] = None, est: Optional[jax.Array] = None,
             down: Optional[jax.Array] = None, placement=None) -> Members:
    """The one program that builds and drains a decision's forks.

    The snapshot is broadcast to ``W·k`` forks, fork ``f = w·k + p``
    running policy p under member w, whose estimates are row w of
    ``est`` (W, J) and whose capacity is down by ``down[w]`` nodes
    (None: unperturbed).  ``width=None`` is the nominal decide: one
    future and no member axis.  All forks drain in one lock-step loop,
    here or under ``placement``; the goal's cost is evaluated per
    member, so rank-based goals rank within a member."""
    k = pool_size(pool)
    eval_mask = state.jobs.state == QUEUED
    states = broadcast_state(state, (width or 1) * k)
    if est is not None:
        states = states._replace(jobs=states.jobs._replace(
            est_runtime=jnp.repeat(est, k, axis=0)))
    if down is not None:
        down = jnp.repeat(down, k)
        states = states._replace(
            free_nodes=jnp.maximum(states.free_nodes - down, 0),
            total_nodes=jnp.maximum(states.total_nodes - down, 1))
    if width is not None:
        pool = tile_pool(pool, width)
        plan = plan * width if plan is not None else None
    if placement is None:
        res = _drain_impl(engine, states, pool, plan)
    else:                       # per shard of the fork axis (§9)
        mesh, axis = placement
        local = shard_local_plan(plan, mesh.shape[axis])
        res = on_shards(
            lambda st, pl, hoisted=None: _drain_impl(
                engine, st, pl, local, hoisted=hoisted),
            mesh, axis, local, states.jobs.state == QUEUED, states, pool)
    with jax.named_scope("select"):
        metrics = jax.vmap(drain_metrics, in_axes=(0, None))(res, eval_mask)
        dead, first0 = res.deadlocked, res.first_started
        if width is not None:
            metrics, dead = jax.tree.map(
                lambda x: x.reshape(width, k), (metrics, dead))
            first0 = first0[:k]
        costs = jnp.where(dead, jnp.inf, objective.costs(metrics))
        return Members(metrics, dead, costs, first0)


def _decision(objective: Objective, m: Members,
              mean_cost: bool = False) -> Decision:
    """The one reduction from members to a packed ``Decision``.  Over a
    member axis the policy cost is the goal's distributional reduction
    of the member costs (``mean_cost``: the cost of the members' mean
    metrics instead, the ensemble's rule), any deadlocked member marks
    its policy deadlocked, and the decision carries the members'
    ``member_uncertainty``.  The qrun set is member 0's, the winner's."""
    with jax.named_scope("select"):
        metrics, dead, costs = m.metrics, m.deadlocked, m.costs
        ci = spread = None
        if dead.ndim == 2:
            metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), metrics)
            dead = jnp.any(dead, axis=0)
            if mean_cost:
                costs = jnp.where(dead, jnp.inf, objective.costs(metrics))
            else:
                costs = as_distributional(objective).reduce_fan(m.costs)
            ci, spread = member_uncertainty(m.costs, axis=0)
        best = scoring.select_policy(costs)
        return Decision(
            policy_index=best,
            costs=costs,
            run_mask=m.first0[best],
            metrics=metrics,
            deadlocked=dead,
            cost_terms=objective.cost_terms(metrics),
            cost_ci=ci,
            fan_width=spread,
        ).packed()


@functools.partial(jax.jit,
                   static_argnames=("engine", "objective", "plan",
                                    "placement"))
def _decide(engine: DrainEngine, state: SimState, pool: EnginePool,
            objective: Objective = DEFAULT_OBJECTIVE,
            plan: HoistPlan = None, placement=None) -> Decision:
    return _decision(objective, _members(engine, state, pool, objective,
                                         plan, placement=placement))


@functools.partial(jax.jit,
                   static_argnames=("engine", "n_ens", "noise", "objective",
                                    "plan"))
def _decide_ensemble(engine: DrainEngine, state: SimState, pool: EnginePool,
                     key: jax.Array, n_ens: int, noise: float,
                     objective: Objective = DEFAULT_OBJECTIVE,
                     plan: HoistPlan = None) -> Decision:
    """The ensemble's member source: member e's estimates under
    lognormal noise from ``key``'s normals, member 0 exact (so the
    qrun set is the mirror's); the policy cost is the cost of the
    members' mean metrics."""
    eps = jax.random.normal(key, (n_ens, state.jobs.capacity))
    est = lognormal_noise(state.jobs.est_runtime[None, :], eps, noise,
                          jnp.arange(n_ens) == 0)
    m = _members(engine, state, pool, objective, plan, n_ens, est=est)
    return _decision(objective, m, mean_cost=True)


@functools.partial(jax.jit,
                   static_argnames=("engine", "spec", "objective", "plan",
                                    "lo", "width"))
def _fan_members(engine: DrainEngine, state: SimState, pool: EnginePool,
                 spec: FanSpec, objective: Objective, plan: HoistPlan,
                 lo: int, width: int) -> Members:
    """Members ``φ ∈ [lo, lo+width)`` of a decision's fan (fork
    ``f = w·k + p``, member ``φ = lo + w``): a racing rung
    (``race.decide_race``), or with ``[0, F)`` the whole fan.  The
    per-member costs are the goal's inner costs; reduction happens in
    the caller."""
    est, down = _fan_draws(state, spec, lo, width)
    return _members(engine, state, pool, objective, plan, width, est, down)


@functools.partial(jax.jit,
                   static_argnames=("engine", "spec", "objective", "plan"))
def _decide_fan(engine: DrainEngine, state: SimState, pool: EnginePool,
                spec: FanSpec = FanSpec(),
                objective: Objective = DEFAULT_OBJECTIVE,
                plan: HoistPlan = None) -> Decision:
    return _decision(objective, _fan_members(engine, state, pool, spec,
                                             objective, plan, 0, spec.n))


# ----------------------------------------------------------------------
# Scenario-vectorized replay (DESIGN.md §6).
# ----------------------------------------------------------------------

def _assemble_replay_inputs(submit, nodes, est, true_rt, valid, totals,
                            pool: EnginePool, P: int):
    """Scenario-row arrays (S, J) -> the flat (k = S·P) replay inputs:
    each row repeats P times (fork f = s·P + p), the pool tiles once
    per row, and the job table is preloaded but fully INVALID.  Pure
    ops — called inside ``_tiled_replay_inputs`` AND the fan jits
    (where the rows are device-perturbed pseudo-scenarios), so both
    paths assemble bit-identically."""
    rep = lambda x: jnp.repeat(x, P, axis=0)
    submit = rep(submit)                                    # (S*P, J)
    valid = rep(valid)
    k, J = submit.shape
    # distinct buffers per leaf (no aliasing): ``states`` is DONATED to
    # the jitted replay, and XLA rejects donating one buffer twice
    none = lambda: jnp.full((k, J), TIME_NONE, dtype=jnp.float32)
    jobs = JobTable(
        submit_t=submit,
        nodes=rep(nodes),
        est_runtime=rep(est),
        start_t=none(),
        end_t=none(),
        state=jnp.zeros((k, J), dtype=jnp.int32),           # INVALID
    )
    states = SimState(jobs=jobs,
                      free_nodes=rep(totals),
                      total_nodes=rep(totals),
                      now=jnp.zeros((k,), dtype=jnp.float32))
    arrival_t = jnp.where(valid, submit, jnp.inf)
    S = totals.shape[0]
    return states, arrival_t, rep(true_rt), tile_pool(pool, S), valid


@functools.partial(jax.jit, static_argnames=("P",))
def _tiled_replay_inputs(submit, nodes, est, true_rt, valid, totals,
                         pool: EnginePool, P: int):
    """The tiling proper, jitted so the ~10 repeat/fill ops fuse into
    one dispatch (eager per-op dispatch used to cost as much as the
    replay itself at small S·P)."""
    return _assemble_replay_inputs(submit, nodes, est, true_rt, valid,
                                   totals, pool, P)


#: Per-``ScenarioSet`` memo of the UNTILED device conversions (the six
#: ``jnp.asarray`` host->device transfers).  Keyed on object identity,
#: evicted by ``weakref.finalize`` when the set dies — never on raw id
#: reuse.  Only the untiled buffers are safe to reuse: the tiled
#: ``states`` is DONATED to the jitted replay, so ``replay_inputs``
#: reruns the (jitted, ~free) tiling per call to mint fresh donatable
#: buffers.  Callers must not mutate a ``ScenarioSet``'s arrays after
#: its first replay (``stack_scenarios`` fills them before returning).
_SCENARIO_ARRAY_CACHE: Dict[int, Tuple] = {}


def _scenario_arrays(scenarios) -> Tuple:
    import weakref
    key = id(scenarios)
    hit = _SCENARIO_ARRAY_CACHE.get(key)
    if hit is not None:
        return hit
    cvt = lambda x, dt: jnp.asarray(x, dtype=dt)
    arrs = (cvt(scenarios.submit_t, jnp.float32),
            cvt(scenarios.nodes, jnp.int32),
            cvt(scenarios.est_runtime, jnp.float32),
            cvt(scenarios.true_runtime, jnp.float32),
            cvt(scenarios.valid, bool),
            cvt(scenarios.total_nodes, jnp.int32))
    try:
        weakref.finalize(scenarios, _SCENARIO_ARRAY_CACHE.pop, key, None)
    except TypeError:
        return arrs          # un-weakref-able stand-in: serve uncached
    _SCENARIO_ARRAY_CACHE[key] = arrs
    return arrs


def replay_inputs(scenarios, pool: EnginePool):
    """Device inputs for the flat (k = S·P) replay batch from a
    ``workload.ScenarioSet``-shaped object: scenario rows repeat P times
    (fork f = s·P + p), the pool tiles once per scenario, and the job
    table is preloaded but fully INVALID — arrivals inject slots as the
    replay reaches them.  Shared by ``DrainEngine.replay_grid`` and
    ``whatif.sharded_replay_grid`` (which shards the leading axis).
    The host->device conversion of the scenario arrays is memoized per
    ``ScenarioSet`` identity (``_scenario_arrays``); the tiling reruns
    per call because its output is donated."""
    P = pool_size(pool)
    return _tiled_replay_inputs(*_scenario_arrays(scenarios), pool, P)


def grid_select(objective: Objective, metrics: DrainMetrics,
                deadlocked: jax.Array, P: int):
    """Per-objective selection over a flat (k = S·P) replay batch:
    reshape the metric fields to (S, P), compile the goal's costs over
    the policy axis (deadlocked forks at +inf), argmin per scenario.
    Pure device code — called inside the jitted replay; the sharded
    streamer calls the jitted ``grid_select_jit`` below (op-by-op eager
    dispatch loses XLA's fused-multiply-add contraction of the score
    arithmetic, breaking cost bitwise-parity with the local path)."""
    grid = jax.tree.map(lambda x: x.reshape((-1, P) + x.shape[1:]), metrics)
    with jax.named_scope("select"):
        costs = objective.costs(grid)                          # (S, P)
        costs = jnp.where(deadlocked.reshape(-1, P), jnp.inf, costs)
        return costs, jnp.argmin(costs, axis=-1)


@functools.partial(jax.jit, static_argnames=("objective", "P"))
def grid_select_jit(objective: Objective, metrics: DrainMetrics,
                    deadlocked: jax.Array, P: int):
    return grid_select(objective, metrics, deadlocked, P)


def member_uncertainty(member_costs: jax.Array, axis: int = -2):
    """``(ci, width)`` over the fan axis of per-member costs: the 95%
    normal CI half-width of the member mean (``1.96·σ/√F``) and the
    worst−best member spread.  Any non-finite member (a deadlocked
    future) poisons both stamps to +inf — "not sure at all"."""
    F = member_costs.shape[axis]
    finite = jnp.all(jnp.isfinite(member_costs), axis=axis)
    safe = jnp.where(jnp.isfinite(member_costs), member_costs, 0.0)
    ci = 1.96 * jnp.std(safe, axis=axis) / np.sqrt(F)
    width = (jnp.max(member_costs, axis=axis)
             - jnp.min(member_costs, axis=axis))
    return (jnp.where(finite, ci, jnp.inf),
            jnp.where(finite, width, jnp.inf))


def fan_select(objective: ObjectiveLike, metrics: DrainMetrics,
               deadlocked: jax.Array, F: int, P: int):
    """Distributional selection over a flat (k = S·F·P) fan batch:
    reshape to (S, F, P), evaluate the inner goal per member
    (deadlocked members at +inf), reduce the fan axis with the goal's
    ``Distributional`` reduction (plain goals lift to ``mean:``), and
    argmin per scenario.  F is static, so the sorted-reduction indices
    are trace-time constants — pure device code, called inside the
    fan jit (the sharded streamer uses ``fan_select_jit``).

    Returns ``(member_costs (S,F,P), costs (S,P), best (S,), ci, width)``.
    """
    dist = as_distributional(objective)
    grid = jax.tree.map(
        lambda x: x.reshape((-1, F, P) + x.shape[1:]), metrics)
    with jax.named_scope("select"):
        member = dist.member_costs(grid)                   # (S, F, P)
        member = jnp.where(deadlocked.reshape(-1, F, P), jnp.inf, member)
        costs = dist.reduce_fan(member)                    # (S, P)
        best = jnp.argmin(costs, axis=-1)
        ci, width = member_uncertainty(member, axis=-2)
        return member, costs, best, ci, width


@functools.partial(jax.jit, static_argnames=("objective", "F", "P"))
def fan_select_jit(objective: Objective, metrics: DrainMetrics,
                   deadlocked: jax.Array, F: int, P: int):
    return fan_select(objective, metrics, deadlocked, F, P)


def _replay_impl(engine: DrainEngine, states: SimState,
                 arrival_t: jax.Array, true_rt: jax.Array,
                 pool: EnginePool, valid: jax.Array,
                 plan: HoistPlan = None,
                 hoisted: Optional[jax.Array] = None):
    # Every slot with a finite arrival will be queued at some point
    # (plus any slot already queued at entry): the hoist domain.
    ever_queued = jnp.isfinite(arrival_t) | (states.jobs.state == QUEUED)
    order_fn = make_order_fn(states, pool, plan, ever_queued=ever_queued,
                             hoisted=hoisted)
    res = simulate_replay_batched(
        states, arrival_t, true_rt, order_fn, engine.pass_fn(),
        dynamic_bounds=engine.dynamic_bounds,
        elide_empty=engine.elide_empty)
    with jax.named_scope("select"):
        metrics = jax.vmap(state_metrics)(res.state, valid, true_rt)
    return res, metrics


@_quiet_donation
@functools.partial(jax.jit,
                   static_argnames=("engine", "plan", "objective", "P"),
                   donate_argnames=("states",))
def _replay(engine: DrainEngine, states: SimState, arrival_t: jax.Array,
            true_rt: jax.Array, pool: EnginePool, valid: jax.Array,
            plan: HoistPlan = None,
            objective: Objective = DEFAULT_OBJECTIVE, P: int = 1):
    res, metrics = _replay_impl(engine, states, arrival_t, true_rt, pool,
                                valid, plan)
    costs, best = grid_select(objective, metrics, res.deadlocked, P)
    return res, metrics, costs, best


@functools.partial(jax.jit,
                   static_argnames=("engine", "plan", "objective", "P",
                                    "S", "spec", "lo", "width"))
def _fan_window_replay(engine: DrainEngine, submit, nodes, est, true_rt,
                       valid, totals, pool: EnginePool,
                       plan: HoistPlan = None,
                       objective: Objective = DEFAULT_OBJECTIVE,
                       P: int = 1, S: int = 1, spec: FanSpec = FanSpec(),
                       lo: int = 0, width: int = 1):
    """The fused fan window: perturbation expansion + replay +
    distributional selection in ONE compiled computation, over members
    ``φ ∈ [lo, lo+width)`` (the whole fan is ``[0, F)``).  Row
    ``r = s·width + w`` is member ``lo + w`` of scenario s (fork
    ``f = r·P + p``); the per-member draws key on (seed, s, φ) alone,
    so each row is bitwise the ``s·F + φ`` row of the full fan.  Only
    the UNTILED base (S, J) arrays cross host->device (H2D is O(1) in
    F), and every expanded buffer is born inside the jit.  ``lo`` and
    ``width`` are static: a rung schedule is fixed, so each rung shape
    compiles once."""
    r = jnp.arange(S * width)
    rows = perturb_window(submit, nodes, est, true_rt, valid, totals,
                          spec, r, lo, width, S)
    states, arrival_t, true_rep, pool_t, valid_rep = \
        _assemble_replay_inputs(*rows, pool, P)
    res, metrics = _replay_impl(engine, states, arrival_t, true_rep,
                                pool_t, valid_rep, plan)
    return res, metrics, fan_select(objective, metrics, res.deadlocked,
                                    width, P)


def _fan_outcome(res: ReplayResult, metrics: DrainMetrics, shape,
                 selected) -> FanOutcome:
    """A flat (k = S·F·P) fan replay and its ``fan_select`` as an
    (S, F, P) ``FanOutcome``."""
    member, costs, best, ci, width = selected
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


def _shape_outcome(res: ReplayResult, metrics: DrainMetrics, shape,
                   costs: Optional[jax.Array] = None,
                   best: Optional[jax.Array] = None) -> ReplayOutcome:
    rs = lambda x: x.reshape(shape + x.shape[1:])
    return ReplayOutcome(
        start_t=rs(res.state.jobs.start_t),
        end_t=rs(res.state.jobs.end_t),
        metrics=jax.tree.map(rs, metrics),
        deadlocked=rs(res.deadlocked),
        events=rs(res.events),
        result=res,
        costs=costs.reshape(shape) if costs is not None else None,
        best=best.reshape(shape[:-1]) if best is not None else None,
    )


@functools.partial(jax.jit, static_argnames=("engine",))
def _single_pass(engine: DrainEngine, state: SimState,
                 pool: EnginePool) -> jax.Array:
    # The emulator's per-event oracle path: deliberately uncompacted
    # (full static rank bound, fresh sort) — it is what the compacted
    # loops are parity-tested against.
    states = broadcast_state(state, 1)
    order = batched_priority_order(states, pool)
    return engine.pass_fn()(states, order, None)[0]


DEFAULT_ENGINE = DrainEngine(backend="reference")
