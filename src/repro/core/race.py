"""Adaptive fan racing: successive halving over the fan substrate
(DESIGN.md §11).

A fixed-F fan (§10) spends ``S·F·P`` members per decision no matter how
obvious the winner is.  Racing spends members only where the decision
is still statistically open: every policy starts at a low rung ``F₀``;
after each rung the per-policy costs and CIs over the members so far
are computed ON DEVICE (``rung_stats`` — the goal's distributional
reduction plus ``engine.member_uncertainty``); policies whose CI lower
bound exceeds the incumbent's CI upper bound are eliminated; the fan
doubles for survivors.  The unlock is the §10 CRN prefix-stability:
member draws key on ``fold_in(fold_in(key, s), φ)`` — independent of F
— so rung i+1 replays ONLY the new member suffix
(``engine.fan_window_grid`` / ``_decide_fan_window``) and concatenates
it with the donated prior-rung members.  No (scenario, policy, member)
triple is ever replayed twice.

Elimination rule (per scenario s, incumbent i = argmin cost):

    drop p  iff  cost[s,p] − z·σₚ/√f  >  cost[s,i] + z·σᵢ/√f   (strict)

Strict ``>`` means exact ties (CRN-identical member costs) never
eliminate each other, and a non-finite bound (a +inf member poisons the
CI to +inf) never eliminates — deadlock-tainted policies survive to
full fidelity rather than being guessed away.  A policy leaves the
replay rectangle only when eliminated in EVERY scenario; the incumbent
of any scenario is never eliminated there, so each scenario's running
winner always survives to the end and the final argmin is unchanged by
the drops.  With an unbounded budget the race therefore returns the
same argmin as the full-F ``fan_grid`` on every (scenario, objective)
cell whenever the CI rule held — property-tested (tests/test_race.py)
and gated per workload by ``benchmarks/race.py``, not assumed.

Termination is ANYTIME: the race stops early when every scenario's
winner CI-separates from all surviving rivals (``separation > 0``), or
when ``RaceSpec.budget_ms`` / ``max_members`` is exhausted mid-race —
in every case returning the current best with its achieved confidence
(``RaceOutcome.separation``/``stopped``).  Rung windows are a fixed
schedule (``RaceSpec.rungs()``), so each (rung width, survivor count)
pair compiles once and is reused across cycles.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fan import FanSpec, normalize_fan
from repro.core.telemetry import fetch

__all__ = [
    "RaceSpec", "RungRecord", "RaceOutcome", "normalize_race",
    "rung_stats", "race_grid", "decide_race",
]


@dataclasses.dataclass(frozen=True)
class RaceSpec:
    """Racing schedule over a ``FanSpec``'s members.

    ``fan.n`` is F_max — the full-fidelity fan a non-raced ``fan_grid``
    would evaluate (and the fidelity survivors reach when nothing
    separates).  Frozen + hashable, like every other static config.
    """

    fan: FanSpec = FanSpec(n=64)
    f0: int = 8                # rung-0 members (capped at fan.n)
    growth: int = 2            # fan multiplier between rungs
    z: float = 1.96            # CI multiplier for elimination/separation
    budget_ms: Optional[float] = None   # wall-clock budget per race
    max_members: Optional[int] = None   # (s, φ, p) triple budget per race

    def __post_init__(self) -> None:
        if self.f0 < 1:
            raise ValueError(f"f0 must be >= 1, got {self.f0}")
        if self.growth < 2:
            raise ValueError(f"growth must be >= 2, got {self.growth}")
        if self.z <= 0.0:
            raise ValueError("z must be positive")
        if self.budget_ms is not None and self.budget_ms <= 0.0:
            raise ValueError("budget_ms must be positive")
        if self.max_members is not None and self.max_members < 1:
            raise ValueError("max_members must be >= 1")

    @property
    def f_max(self) -> int:
        return self.fan.n

    def rungs(self) -> Tuple[Tuple[int, int], ...]:
        """The fixed member-window schedule ``[(0, F₀), (F₀, F₀·g),
        ...]``, capped at F_max — rung i replays ONLY window
        ``[lo, hi)``; cumulative fidelity after rung i is ``hi``."""
        hi = min(self.f0, self.f_max)
        out = [(0, hi)]
        while hi < self.f_max:
            lo, hi = hi, min(hi * self.growth, self.f_max)
            out.append((lo, hi))
        return tuple(out)


def normalize_race(race) -> RaceSpec:
    """Accept a ``RaceSpec``, a ``FanSpec`` (raced to ``spec.n`` under
    the default schedule), or a bare int F_max (degenerate fan)."""
    if isinstance(race, RaceSpec):
        return race
    return RaceSpec(fan=normalize_fan(race))


class RungRecord(NamedTuple):
    """Accounting for one executed rung."""
    lo: int                      # member window replayed: [lo, hi)
    hi: int
    active: Tuple[int, ...]      # full-pool indices evaluated this rung
    members: int                 # (s, φ, p) triples replayed this rung
    eliminated: Tuple[int, ...]  # indices dropped from the rectangle
    separation: float            # min-scenario rival_lb − winner_ub
    wall_s: float


class RaceOutcome(NamedTuple):
    """What a race decided and what it paid (host-side: the race
    controller is a host loop over device rungs, so the arrays land as
    numpy).  Policy columns cover the SURVIVING rectangle ``keep``
    (full-pool indices, ascending); ``best`` is already mapped back to
    full-pool indices."""
    member_costs: np.ndarray     # (S, fan_size, len(keep)) accumulated
    costs: np.ndarray            # (S, len(keep)) reduced at fan_size
    best: np.ndarray             # (S,) winners as FULL-pool indices
    cost_ci: np.ndarray          # (S, len(keep)) z-scaled CI half-width
    fan_width: np.ndarray        # (S, len(keep)) member-cost spread
    keep: np.ndarray             # surviving full-pool indices
    rungs: Tuple[RungRecord, ...]
    members: int                 # triples replayed across all rungs
    members_full: int            # S·F_max·P — the fixed-F bill
    fan_size: int                # members behind costs (last rung's hi)
    separated: bool              # every scenario separated at the end
    separation: np.ndarray       # (S,) achieved rival_lb − winner_ub
    stopped: str  # 'separated' | 'budget_ms' | 'max_members' | 'exhausted'
    passes: int = 0              # DES pass_invocations across all rungs
    #                              (0 on surfaces that don't expose it)


@functools.partial(jax.jit, static_argnames=("dist",))
def _rung_stats_impl(dist, member: jax.Array, scale: float):
    from repro.core.engine import member_uncertainty
    costs = dist.reduce_fan(member)
    ci, width = member_uncertainty(member, axis=-2)
    return costs, ci * scale, width


def rung_stats(objective, member, z: float = 1.96):
    """Per-policy decision stats over the members accumulated so far:
    the goal's distributional reduction (what the argmin selects) plus
    the z-scaled CI half-width and member spread — computed on device
    (``engine.member_uncertainty`` emits ``1.96·σ/√f``; rescaled to
    ``z``).  ``member`` is (S, f, Pa); any +inf member poisons that
    cell's CI/width to +inf, which the elimination rule treats as
    "never eliminate"."""
    from repro.core.objective import as_distributional
    dist = as_distributional(objective)
    return _rung_stats_impl(dist, jnp.asarray(member), z / 1.96)


def _separation(costs: np.ndarray, ci: np.ndarray) -> np.ndarray:
    """(S,) how far the winner's CI upper bound sits below EVERY
    rival's lower bound (min over rivals); positive ⇒ the scenario's
    decision is settled at z confidence.  +inf with a single column;
    non-finite bound arithmetic (inf − inf) counts as unseparated."""
    S, Pa = costs.shape
    if Pa == 1:
        return np.full(S, np.inf, np.float32)
    with np.errstate(invalid="ignore"):
        lb = costs - ci
        ub = costs + ci
        rows = np.arange(S)
        inc = np.argmin(costs, axis=1)
        lb_rivals = lb.copy()
        lb_rivals[rows, inc] = np.inf
        sep = lb_rivals.min(axis=1) - ub[rows, inc]
    return np.where(np.isnan(sep), -np.inf, sep).astype(np.float32)


def run_race(spec: RaceSpec, S: int, P: int, objective,
             eval_window: Callable[[np.ndarray, int, int], np.ndarray],
             on_rung: Optional[Callable] = None,
             clock: Callable[[], float] = time.perf_counter
             ) -> RaceOutcome:
    """The racing controller, shared by the grid, sharded, and drain
    surfaces.  ``eval_window(active, lo, hi)`` replays ONLY members
    ``φ ∈ [lo, hi)`` for the full-pool indices ``active`` and returns
    their (S, hi−lo, len(active)) member costs (+inf-poisoned for
    deadlocks); everything else — accumulation, CI elimination,
    separation, budgets — happens here, identically on every surface.
    ``on_rung(active, costs, ci, width)`` (post-rung, pre-drop) lets
    callers mirror per-policy stats for eliminated columns."""
    schedule = spec.rungs()
    active = np.arange(P)
    elim = np.zeros((S, P), bool)        # per-scenario CI eliminations
    buf = np.full((S, spec.f_max, P), np.nan, np.float32)
    rungs = []
    spent = 0
    rows = np.arange(S)
    t0 = clock()
    stopped = "exhausted"
    costs = ci = width = None
    f_done = 0

    for lo, hi in schedule:
        w = hi - lo
        if lo > 0:       # rung 0 always runs: anytime ⇒ SOME answer
            if (spec.budget_ms is not None
                    and (clock() - t0) * 1e3 >= spec.budget_ms):
                stopped = "budget_ms"
                break
            if (spec.max_members is not None
                    and spent + S * w * len(active) > spec.max_members):
                stopped = "max_members"
                break
        t_r = clock()
        # Prefix-reuse invariant: the window being paid for has never
        # been evaluated (the buffer cell is still NaN).  This is the
        # "no (s, φ, p) triple replayed twice" guarantee, enforced —
        # not assumed — on every surface that goes through run_race.
        if not np.isnan(buf[:, lo:hi, :][:, :, active]).all():
            raise RuntimeError(
                f"racing window [{lo}, {hi}) would replay an already-"
                f"evaluated member")
        mc = np.asarray(fetch(eval_window(active, lo, hi)), np.float32)
        buf[:, lo:hi, active] = mc
        spent += S * w * len(active)
        f_done = hi
        cur = buf[:, :hi, :][:, :, active]           # (S, hi, Pa)
        costs, ci, width = (fetch(x) for x in
                            rung_stats(objective, cur, spec.z))
        if on_rung is not None:
            on_rung(active, costs, ci, width)

        # CI elimination: strict ``>`` (ties survive) on possibly
        # non-finite bounds (``nan > x`` is False — +inf-poisoned CIs
        # never eliminate); each scenario's incumbent is immune there.
        inc = np.argmin(costs, axis=1)
        with np.errstate(invalid="ignore"):
            kill = (costs - ci) > (costs + ci)[rows, inc][:, None]
        kill[rows, inc] = False
        el = elim[:, active] | kill
        elim[:, active] = el
        survives = ~el.all(axis=0)                   # (Pa,)
        dropped = active[~survives]
        sep = _separation(costs, ci)
        rungs.append(RungRecord(
            lo=lo, hi=hi, active=tuple(int(i) for i in active),
            members=S * w * len(active),
            eliminated=tuple(int(i) for i in dropped),
            separation=float(sep.min()), wall_s=clock() - t_r))

        # Restrict the carried stats to survivors so an early budget
        # stop on the NEXT rung still reports a consistent rectangle.
        active = active[survives]
        costs, ci, width = (x[:, survives] for x in (costs, ci, width))
        if len(active) == 1 or sep.min() > 0.0:
            stopped = "separated"
            break

    sep = _separation(costs, ci)
    best_col = np.argmin(costs, axis=1)
    return RaceOutcome(
        member_costs=buf[:, :f_done, :][:, :, active],
        costs=costs,
        best=active[best_col],
        cost_ci=ci,
        fan_width=width,
        keep=active,
        rungs=tuple(rungs),
        members=spent,
        members_full=S * spec.f_max * P,
        fan_size=f_done,
        separated=bool((sep > 0.0).all()),
        separation=sep,
        stopped=stopped,
    )


# ----------------------------------------------------------------------
# Grid surface: the raced replay grid.
# ----------------------------------------------------------------------

def race_grid(scenarios, pool, race, objective=None, *,
              engine=None) -> RaceOutcome:
    """Race the (scenario × policy) fan grid: rung suffixes come from
    ``engine.fan_window_grid`` over the surviving sub-pool (ascending
    indices, so the argmin tie-break matches the full pool's).  With an
    unbounded budget this selects the same winner as the full-F
    ``fan_grid`` on every scenario (module docstring; property-tested).
    """
    from repro.core import engine as _eng
    eng = engine if engine is not None else _eng.DEFAULT_ENGINE
    spec = normalize_race(race)
    from repro.core.objective import resolve_goal
    goal = resolve_goal(objective)
    pool = _eng.as_pool(pool)
    P = _eng.pool_size(pool)
    S = int(scenarios.total_nodes.shape[0])
    sub_pools = {}
    passes = [0]

    def eval_window(active, lo, hi):
        key = tuple(int(i) for i in active)
        sub = sub_pools.get(key)
        if sub is None:
            sub = (pool if len(active) == P
                   else _eng._index_pool(pool, jnp.asarray(active)))
            sub_pools[key] = sub
        out = eng.fan_window_grid(scenarios, sub, spec.fan, goal,
                                  lo=lo, width=hi - lo)
        passes[0] += int(out.result.pass_invocations)
        return out.member_costs

    out = run_race(spec, S, P, goal, eval_window)
    return out._replace(passes=passes[0])


# ----------------------------------------------------------------------
# Drain surface: the raced decision cycle.
# ----------------------------------------------------------------------

def decide_race(state, pool, race, objective=None, *, engine=None):
    """One raced decision cycle: ``decide_fan``'s member fan grown rung
    by rung (``engine._decide_fan_window``) with CI elimination and
    anytime budgets.  Returns ``(Decision, RaceOutcome)`` — the
    decision spans the FULL pool (eliminated policies keep the
    costs/CI from their elimination rung; their members simply stopped
    growing), ``fan_size`` is the fidelity the survivors reached, and
    the qrun set comes from member 0 of the winner (member 0 is exact
    and always in rung 0)."""
    from repro.core import engine as _eng
    from repro.core.objective import as_distributional, resolve_goal
    eng = engine if engine is not None else _eng.DEFAULT_ENGINE
    spec = normalize_race(race)
    goal = resolve_goal(objective)
    dist = as_distributional(goal)
    pool = _eng.as_pool(pool)
    k = _eng.pool_size(pool)

    sub_pools = {}
    full = {"costs": np.full(k, np.inf, np.float32),
            "ci": np.full(k, np.inf, np.float32),
            "width": np.full(k, np.inf, np.float32)}
    dead = np.zeros(k, bool)
    msum = None                      # metric sums per policy (tree)
    mcount = np.zeros(k, np.int64)
    first0 = {}

    def eval_window(active, lo, hi):
        nonlocal msum
        key = tuple(int(i) for i in active)
        sub = sub_pools.get(key)
        if sub is None:
            sub = (pool if len(active) == k
                   else _eng._index_pool(pool, jnp.asarray(active)))
            sub_pools[key] = sub
        mc, md, mm, f0 = _eng._decide_fan_window(
            eng, state, sub, spec.fan, goal, eng.plan(sub),
            lo, hi - lo)
        if lo == 0:
            first0["mask"] = fetch(f0)           # (k, J): rung 0 = full pool
        dead[active] |= fetch(md).any(axis=0)
        sums = jax.tree.map(lambda x: fetch(x).sum(axis=0, dtype=np.float64),
                            mm)
        if msum is None:
            msum = jax.tree.map(lambda s: np.zeros(k, np.float64), sums)
        msum = jax.tree.map(
            lambda acc, s: _scatter_add(acc, active, s), msum, sums)
        mcount[active] += hi - lo
        return fetch(mc)[None]                   # (S=1, W, Pa)

    def on_rung(active, costs, ci, width):
        full["costs"][active] = costs[0]
        full["ci"][active] = ci[0]
        full["width"][active] = width[0]

    out = run_race(spec, 1, k, goal, eval_window, on_rung=on_rung)

    mean_metrics = jax.tree.map(
        lambda s: jnp.asarray(s / np.maximum(mcount, 1), jnp.float32),
        msum)
    best = int(out.best[0])
    decision = _eng.Decision(
        policy_index=jnp.asarray(best),
        costs=jnp.asarray(full["costs"]),
        run_mask=jnp.asarray(first0["mask"][best]),
        metrics=mean_metrics,
        deadlocked=jnp.asarray(dead),
        cost_terms=dist.cost_terms(mean_metrics),
        cost_ci=jnp.asarray(full["ci"]),
        fan_width=jnp.asarray(full["width"]),
        fan_size=out.fan_size,
    )
    return decision, out


def _scatter_add(acc: np.ndarray, idx: np.ndarray, val: np.ndarray):
    acc = acc.copy()
    np.add.at(acc, idx, val)
    return acc
