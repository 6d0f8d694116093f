"""On-device Monte-Carlo scenario fans (DESIGN.md §10).

A replay-grid decision evaluates ONE predicted future per (scenario,
policy) cell — fragile exactly when adaptivity matters: user runtime
estimates are notoriously wrong, clusters lose nodes, and arrival
bursts reshape the queue.  A **fan** evaluates F *perturbed* futures
per cell instead, and selects by a distributional goal
(``objective.Distributional``: ``p95:avg_wait``, ``cvar:0.9:...``,
``worst:``, ``regret:``).

The perf contract is that the fan is expanded **inside the jitted
replay**: the base ``ScenarioSet`` is uploaded once ((S, J) arrays, the
same H2D traffic as a fan-less grid) and the F perturbations are
derived on device from per-member PRNG keys — no host materialization,
padding, or shipping of F trace copies (``benchmarks/risk.py`` gates
the ≥10× H2D reduction — exactly F× by construction — plus the
wall-clock win over that baseline, bitwise member parity included).  Fan member φ of scenario s rides the
existing fork axis as pseudo-scenario ``g = s·F + φ`` (flat fork
``f = g·P + p``), which keeps the §7 hoist plans P-periodic and lets
the §9 fleet streamer shard the fan like any other scenario axis.

Three perturbation models, all gated *statically* on ``FanSpec`` fields
(a zeroed model compiles to the identity, so the degenerate spec is
bit-exact to ``engine.replay_grid``), all keyed per (s, φ)
independently of F (``jax.random.fold_in`` chains — fans are
deterministic, resumable, and **prefix-stable**: the members of a low-F
pre-pass are literally the first members of the full fan, the
common-random-numbers property the pruning below and the CVaR/regret
comparisons across policies rely on):

* ``runtime_noise`` — mean-preserving multiplicative lognormal noise on
  TRUE runtimes (``exp(σ·ε − σ²/2)``): reality diverging from the
  submitted estimates, which stay untouched (the §3.2 asymmetry);
* ``burst_amplitude``/``burst_period`` — a monotone sinusoidal time
  warp of the arrival timeline with a per-member random phase (the
  ``workload.bursty_trace`` rate modulation applied as a time change):
  derivative ``1 + A·cos ≥ 1 − A > 0`` preserves submission order;
* ``failure_prob``/``failure_frac`` — per-member node-failure draws
  against the horizon: with probability ``failure_prob`` the member
  loses ``U[0, failure_frac]`` of its nodes for the whole replay (the
  emulator's ``FailureSpec`` timeline collapsed to its worst case);
  members whose capacity can no longer fit a job legitimately deadlock
  and contribute ``+inf`` member costs.  With ``failure_domains = D >
  0`` the i.i.d. per-member draw is replaced by a CORRELATED
  rack/power-domain model (ROADMAP risk residual c): the cluster is
  split into D equal domains, each domain d of scenario s carries a
  latent fragility ``q[s, d]`` keyed on ``(seed, s, d)`` ONLY — shared
  by every member and persistent across racing rungs, member windows,
  and repeated decisions (the same domains are the weak ones
  everywhere) — and member φ fails exactly the domains whose
  threshold ``min(2·failure_prob·q[s, d], 1)`` exceeds its single
  uniform draw.  Failures therefore arrive in domain-sized chunks,
  member failure sets are NESTED (a more unlucky member loses a
  superset of domains), and members are positively correlated through
  the shared fragilities, while the marginal per-domain failure rate
  stays ``failure_prob`` (exactly for ``failure_prob ≤ 0.5``; clipped
  above).  ``failure_frac`` caps the total fraction lost.  ``D = 0``
  (default) keeps the legacy i.i.d. model bit-for-bit.

Member φ=0 is always EXACT (no perturbation): it is the fan-less
prediction, so an F=1 fan is bitwise the PR-6 replay for ANY spec, and
the distinguished member the twin's qrun actions come from.

**Goal-conditioned pool pruning** (``pruned_fan_grid``): a cheap low-F
pre-pass drops policies a dominance bound proves the objective never
selects, before the full-F grid runs.  The bound is index-guarded
first-order dominance on member costs — policy p is dropped iff in
EVERY scenario some earlier-index policy q is no worse on every sorted
member cost (unsorted/pointwise for ``regret:``, whose per-member best
is CRN-aligned).  Sorted dominance implies ``reduce(q) ≤ reduce(p)``
for every symmetric monotone reduction (quantiles, CVaR, mean, worst),
and the ``q < p`` index guard means q also wins the argmin's
first-occurrence tie-break — so removing p cannot change the selected
policy.  The theorem is exact when the pre-pass fan IS the deciding fan
(``pre_n == n``, the property tested in tests/test_fan.py); for
``pre_n < n`` prefix-stability makes it a strong empirical bound,
gated end-to-end by benchmarks/risk.py (selection identical on every
(scenario, objective) cell).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import fetch

__all__ = [
    "FanSpec", "PruneInfo", "perturb_block", "perturb_rows",
    "perturb_window", "materialize_fan", "dominance_keep",
    "pruned_fan_grid", "normalize_fan", "fit_runtime_sigma",
    "failure_downs",
]


@dataclasses.dataclass(frozen=True)
class FanSpec:
    """How to grow F perturbed futures from one base scenario.

    Frozen + hashable → a static jit argument: each (spec, shape) pair
    compiles once.  All randomness derives from ``seed`` through
    per-(scenario, member) ``fold_in`` chains — no global RNG state,
    same member → same perturbation regardless of F or block slicing.
    """

    n: int = 1                    # fan size F (members per scenario)
    runtime_noise: float = 0.0    # σ of lognormal true-runtime noise
    burst_amplitude: float = 0.0  # arrival warp amplitude A in [0, 1)
    burst_period: float = 3600.0  # arrival warp period (seconds)
    failure_prob: float = 0.0     # P(member loses nodes) in [0, 1]
    failure_frac: float = 0.25    # max fraction of nodes lost
    failure_domains: int = 0      # D rack/power domains (0 = i.i.d.)
    seed: int = 0

    def __post_init__(self) -> None:
        if self.n < 1:
            raise ValueError(f"fan size must be >= 1, got {self.n}")
        if not 0.0 <= self.burst_amplitude < 1.0:
            raise ValueError(
                f"burst_amplitude must be in [0, 1) to keep the arrival "
                f"warp monotone, got {self.burst_amplitude}")
        if self.burst_period <= 0.0:
            raise ValueError("burst_period must be positive")
        if not 0.0 <= self.failure_prob <= 1.0:
            raise ValueError("failure_prob must be in [0, 1]")
        if not 0.0 <= self.failure_frac <= 1.0:
            raise ValueError("failure_frac must be in [0, 1]")
        if self.failure_domains < 0:
            raise ValueError("failure_domains must be >= 0")
        if self.runtime_noise < 0.0:
            raise ValueError("runtime_noise must be >= 0")

    @property
    def degenerate(self) -> bool:
        """True when every perturbation model is off — the fan compiles
        to exactly the base expansion (bitwise ``replay_grid`` parity)."""
        return (self.runtime_noise == 0.0 and self.burst_amplitude == 0.0
                and self.failure_prob == 0.0)

    @classmethod
    def from_history(cls, telemetry, n: int = 64, *,
                     min_samples: int = 8, fallback: float = 0.3,
                     **kwargs) -> "FanSpec":
        """Fit ``runtime_noise`` to the twin's OWN observed §3.2
        estimate-vs-true residuals instead of an administrator guess.

        ``telemetry`` is a ``Telemetry`` (its ``runtime_residuals``
        list, recorded by the twin at every JOBOBIT as ``(estimated,
        actual)`` runtime pairs) or any iterable of such pairs.  The
        lognormal model is exactly the fan's perturbation model
        (``actual = est · exp(σ·ε − σ²/2)`` mean-preserving), so the
        MLE is the sample std of ``log(actual/est)``; until
        ``min_samples`` completions are observed the ``fallback`` σ is
        used.  Host-side fitting only — the returned spec enters the
        device path like any other ``FanSpec``."""
        res = getattr(telemetry, "runtime_residuals", telemetry)
        sigma = fit_runtime_sigma(res, min_samples=min_samples,
                                  fallback=fallback)
        return cls(n=n, runtime_noise=sigma, **kwargs)


def normalize_fan(fan) -> FanSpec:
    """Accept a ``FanSpec`` or a bare int F (a degenerate F-member fan
    — useful for parity tests and CLI defaults)."""
    if isinstance(fan, FanSpec):
        return fan
    return FanSpec(n=int(fan))


def fit_runtime_sigma(residuals, *, min_samples: int = 8,
                      fallback: float = 0.3) -> float:
    """σ̂ = sample std (ddof=1) of ``log(actual/est)`` over the finite
    positive ``(est, actual)`` pairs; ``fallback`` below ``min_samples``
    usable pairs.  Pure host arithmetic."""
    logs = []
    for est, actual in residuals:
        e, a = float(est), float(actual)
        if e > 0.0 and a > 0.0 and np.isfinite(e) and np.isfinite(a):
            logs.append(np.log(a / e))
    if len(logs) < max(min_samples, 2):
        return float(fallback)
    return float(np.std(np.asarray(logs), ddof=1))


# ----------------------------------------------------------------------
# Per-member PRNG derivation.  Key chain: seed -> scenario s -> member φ
# -> draw tag.  φ-keyed (not F-keyed): prefixes are stable.
# ----------------------------------------------------------------------

def _member_draws(seed: int, s: jax.Array, phi: jax.Array, J: int):
    """Perturbation draws for ONE (scenario, member): runtime-noise
    normals (J,), a burst phase scalar, and two uniforms (failure hit +
    severity).  Scalar ``s``/``phi`` — vmapped over the block axis."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), s), phi)
    eps = jax.random.normal(jax.random.fold_in(k, 0), (J,))
    phase = jax.random.uniform(jax.random.fold_in(k, 1), (),
                               minval=0.0, maxval=2.0 * np.pi)
    u = jax.random.uniform(jax.random.fold_in(k, 2), (2,))
    return eps, phase, u


# Domain-fragility key tag: folded where the member φ normally goes, so
# the chain stays (seed → s → ·) but can NEVER collide with a real
# member (fans are orders of magnitude smaller than 2^31 − 1).
_DOMAIN_TAG = 0x7FFFFFFF


def _domain_fragility(seed: int, s: jax.Array, D: int) -> jax.Array:
    """Latent fragilities ``q[s, :] ∈ [0, 1)`` of the D rack/power
    domains of ONE scenario — keyed on ``(seed, s, d)`` only, NO member
    φ in the chain: every member, racing rung window, and repeated
    decision sees the SAME weak domains (persistence across time)."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), s), _DOMAIN_TAG)
    return jax.random.uniform(k, (D,))


def failure_downs(spec: FanSpec, s: jax.Array, phi: jax.Array,
                  u: jax.Array, tot: jax.Array) -> jax.Array:
    """Per-row node-capacity reductions of the failure model — the ONE
    implementation shared by ``perturb_rows`` (replay-side fans) and
    the drain-side ``engine._decide_fan``, so both fan surfaces agree
    on the correlation structure.

    ``u`` is the (B, 2) member uniform pair from ``_member_draws``;
    ``s``/``phi`` are the (B,) scenario/member ids; ``tot`` the (B,)
    capacities.  Returns (B,) reductions in ``tot``'s dtype; exact
    members (φ=0) always get 0.  ``failure_domains == 0`` reproduces
    the legacy i.i.d. draw bit-for-bit; ``D > 0`` is the comonotone
    domain model documented in the module docstring (member φ fails
    domain d iff ``u[φ, 0] < min(2·p·q[s, d], 1)`` — one uniform per
    member thresholded against the shared fragilities, so failure sets
    are nested across members and marginally P(fail) = p per domain
    for p ≤ 0.5), losing ``floor(tot · n_failed / D)`` nodes capped at
    ``floor(tot · failure_frac)``."""
    exact = phi == 0
    totf = tot.astype(jnp.float32)
    if spec.failure_domains > 0:
        D = spec.failure_domains
        q = jax.vmap(functools.partial(
            _domain_fragility, spec.seed, D=D))(s)            # (B, D)
        thresh = jnp.minimum(2.0 * spec.failure_prob * q, 1.0)
        hit_d = (u[:, :1] < thresh) & (~exact)[:, None]       # (B, D)
        n_fail = hit_d.sum(axis=1).astype(jnp.float32)
        down = jnp.floor(totf * (n_fail / D))
        down = jnp.minimum(down, jnp.floor(totf * spec.failure_frac))
    else:
        hit = (u[:, 0] < spec.failure_prob) & ~exact
        frac = u[:, 1] * spec.failure_frac
        down = jnp.where(hit, jnp.floor(totf * frac), 0.0)
    return down.astype(tot.dtype)


def perturb_rows(submit, nodes, est, true_rt, valid, totals,
                 spec: FanSpec, s: jax.Array, phi: jax.Array,
                 inert: jax.Array):
    """Perturb explicit (scenario, member) row vectors — the shared
    core of ``perturb_block`` (contiguous fans, ``φ = g mod F``) and
    ``perturb_window`` (member windows ``φ ∈ [lo, hi)``, the racing
    suffix replays).  Perturbations depend ONLY on ``(spec.seed, s,
    φ)`` — never on how the rows were batched — which is the CRN
    prefix-stability contract the donation/racing paths rely on: a row
    built here is bitwise the same row of the full fan."""
    sub = submit[s]
    nod = nodes[s]
    es = est[s]
    tr = true_rt[s]
    val = valid[s]
    tot = totals[s]

    if not spec.degenerate:
        J = submit.shape[1]
        eps, phase, u = jax.vmap(
            functools.partial(_member_draws, spec.seed, J=J))(s, phi)
        exact = phi == 0
        if spec.runtime_noise > 0.0:
            sig = spec.runtime_noise
            scale = jnp.exp(sig * eps - 0.5 * sig * sig)
            tr = jnp.where(exact[:, None], tr, tr * scale)
        if spec.burst_amplitude > 0.0:
            omega = 2.0 * np.pi / spec.burst_period
            amp = spec.burst_amplitude / omega
            warped = sub + amp * (jnp.sin(omega * sub + phase[:, None])
                                  - jnp.sin(phase)[:, None])
            # monotone in exact arithmetic (derivative >= 1 - A > 0) and
            # >= 0 (|sin(a+d) - sin a| <= d); cummax irons out any f32
            # rounding inversion so the replay's arrival cursor stays
            # valid — and is applied identically by the host oracle
            warped = jax.lax.cummax(warped, axis=1)
            sub = jnp.where(exact[:, None], sub, warped)
        if spec.failure_prob > 0.0:
            down = failure_downs(spec, s, phi, u, tot)
            tot = jnp.maximum(tot - down, 1)

    val = val & ~inert[:, None]
    tot = jnp.where(inert, jnp.ones_like(tot), tot)
    return sub, nod, es, tr, val, tot


def perturb_block(submit, nodes, est, true_rt, valid, totals,
                  spec: FanSpec, g: jax.Array, S: int):
    """Expand base (S, J) scenario arrays into a block of perturbed
    pseudo-scenarios — pure device code, called INSIDE the fan jits.

    ``g`` is the (G,) pseudo-scenario id vector (``g = s·F + φ``); ids
    past ``S·F`` become INERT rows (valid all-False, ``total_nodes=1``,
    the ``pad_scenarios`` convention) so the fleet streamer can pad its
    last block.  Member φ=0 selects the unperturbed base bitwise
    (``jnp.where``, not arithmetic), and each model is gated on a
    static Python ``if`` — a degenerate spec compiles to the plain
    gather, which is how F=1 parity with ``replay_grid`` is bit-exact.
    """
    F = spec.n
    inert = g >= S * F
    gc = jnp.minimum(g, S * F - 1)
    s, phi = gc // F, gc % F
    return perturb_rows(submit, nodes, est, true_rt, valid, totals,
                        spec, s, phi, inert)


def perturb_window(submit, nodes, est, true_rt, valid, totals,
                   spec: FanSpec, r: jax.Array, lo, width: int, S: int):
    """Expand ONLY members ``φ ∈ [lo, lo+width)`` of each scenario —
    the racing suffix: row ``r = s·width + w`` is member ``φ = lo + w``
    of scenario s, bitwise the row ``s·F + φ`` of the full fan
    (``perturb_rows`` keys on (s, φ) alone).  ``lo`` may be a traced
    scalar so fleet blocks at different offsets share one compile; ids
    past ``S·width`` are inert padding rows as in ``perturb_block``."""
    inert = r >= S * width
    rc = jnp.minimum(r, S * width - 1)
    s, w = rc // width, rc % width
    return perturb_rows(submit, nodes, est, true_rt, valid, totals,
                        spec, s, lo + w, inert)


# ----------------------------------------------------------------------
# Host materialization — the bit-exact oracle (and the benchmark's
# naive baseline): the SAME per-member perturbations pulled to host and
# packed as an (S·F)-scenario ScenarioSet for the fan-less replay_grid.
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "S"))
def _materialize_arrays(submit, nodes, est, true_rt, valid, totals,
                        spec: FanSpec, S: int):
    g = jnp.arange(S * spec.n)
    return perturb_block(submit, nodes, est, true_rt, valid, totals,
                         spec, g, S)


def materialize_fan(scenarios, spec: FanSpec):
    """The fan as a plain host-side ``ScenarioSet`` of S·F
    pseudo-scenarios (row ``s·F + φ`` = member φ of scenario s), with
    the IDENTICAL device-derived perturbations — so
    ``replay_grid(materialize_fan(sc, spec), pool)`` is bitwise equal
    to ``fan_grid(sc, pool, spec)`` member metrics (tests/test_fan.py).
    This is what the naive host path has to build, pad, and ship per
    decision; ``benchmarks/risk.py`` times it as the baseline."""
    S = int(scenarios.total_nodes.shape[0])
    arrs = (jnp.asarray(scenarios.submit_t, jnp.float32),
            jnp.asarray(scenarios.nodes, jnp.int32),
            jnp.asarray(scenarios.est_runtime, jnp.float32),
            jnp.asarray(scenarios.true_runtime, jnp.float32),
            jnp.asarray(scenarios.valid, bool),
            jnp.asarray(scenarios.total_nodes, jnp.int32))
    sub, nod, es, tr, val, tot = (np.asarray(x) for x in
                                  _materialize_arrays(*arrs, spec, S))
    return dataclasses.replace(
        scenarios, submit_t=sub, nodes=nod, est_runtime=es,
        true_runtime=tr, valid=val,
        n_jobs=np.repeat(np.asarray(scenarios.n_jobs), spec.n),
        total_nodes=tot)


# ----------------------------------------------------------------------
# Goal-conditioned pool pruning.
# ----------------------------------------------------------------------

class PruneInfo(NamedTuple):
    """What the pre-pass dropped and how the sub-grid maps back."""
    keep: np.ndarray        # kept FULL-pool indices, ascending
    best: np.ndarray        # (S,) winners as FULL-pool indices
    rate: float             # fraction of the pool pruned
    pre_members: np.ndarray  # (S, pre_n, P) pre-pass member costs
    members: int = 0        # (s, φ, p) triples actually replayed
    members_full: int = 0   # triples an unpruned full fan replays


def dominance_keep(member_costs: np.ndarray,
                   pointwise: bool = False) -> np.ndarray:
    """(P,) keep mask from (S, F0, P) member costs.

    Policy p is DROPPED iff in every scenario some policy q with
    ``q < p`` (pool order — the argmin tie-break) satisfies
    ``c[s, ·, q] <= c[s, ·, p]`` on every member — over SORTED member
    costs for the symmetric monotone reductions (first-order stochastic
    dominance), or raw CRN-aligned members for ``regret:``
    (``pointwise=True``; removing a pointwise-dominated policy leaves
    every member's per-policy min unchanged).  The index guard makes
    dominance a sub-relation of pool order: acyclic, and the surviving
    argmin equals the full-pool argmin (module docstring).  ``inf``
    member costs (deadlocks) compare like any value; NaNs never
    dominate."""
    c = np.asarray(member_costs, dtype=np.float64)
    if c.ndim != 3:
        raise ValueError(f"member costs must be (S, F, P), got {c.shape}")
    if not pointwise:
        c = np.sort(c, axis=1)
    # le[s, q, p]: q no worse than p on every (sorted) member of s
    le = (c[:, :, :, None] <= c[:, :, None, :]).all(axis=1)
    P = c.shape[-1]
    earlier = np.arange(P)[:, None] < np.arange(P)[None, :]   # q < p
    dominated = (le & earlier).any(axis=1)                    # (S, P)
    return ~dominated.all(axis=0)


def pruned_fan_grid(scenarios, pool, fan, objective=None, *,
                    engine=None, pre_n: int = 16):
    """Two-pass fan evaluation: a cheap ``pre_n``-member pre-pass, the
    dominance prune, then ONLY the remaining member suffix
    ``φ ∈ [pre_n, F)`` over the kept sub-pool.

    The pre-pass members are DONATED into the deciding fan via CRN
    prefix-stability — member φ of the pre-pass is bitwise member φ of
    the full fan (``perturb_rows`` keys on (s, φ) alone), so the
    donated prefix concatenates with the ``fan_window_grid`` suffix
    into exactly the full fan's member grid without replaying any
    (scenario, policy, member) triple twice.  ``info.members`` vs
    ``info.members_full`` accounts for the saving: the old double-pay
    was ``S·(pre_n·P + F·P_kept)``; donation makes it
    ``S·(pre_n·P + (F − pre_n)·P_kept)`` — with ``pre_n == F`` the
    second pass vanishes entirely.

    Returns ``(outcome, info)`` — ``outcome`` is the full-F
    ``engine.FanOutcome`` over the KEPT pool (its ``costs``/``metrics``
    have ``len(info.keep)`` policy columns; its ``result`` is None —
    the outcome is assembled from donated pieces, not one flat
    replay); ``info.best`` maps the per-scenario winners back to
    FULL-pool indices.  Selection is bitwise identical to the
    pre-donation double-replay (tests/test_fan.py asserts member
    parity against the unpruned grid); with ``pre_n == fan.n`` the
    winner is provably identical to the unpruned grid."""
    from repro.core import engine as _eng
    from repro.core.objective import as_distributional, resolve_goal
    eng = engine if engine is not None else _eng.DEFAULT_ENGINE
    spec = normalize_fan(fan)
    goal = resolve_goal(objective)
    pool = _eng.as_pool(pool)
    pre = dataclasses.replace(spec, n=min(pre_n, spec.n))
    pre_out = eng.fan_grid(scenarios, pool, pre, goal)
    pre_members = fetch(pre_out.member_costs)
    pointwise = as_distributional(goal).reduction == "regret"
    keep = dominance_keep(pre_members, pointwise=pointwise)
    keep_idx = np.nonzero(keep)[0]
    P = keep.shape[0]
    Pk = len(keep_idx)
    S = int(scenarios.total_nodes.shape[0])
    sub_pool = (pool if Pk == P
                else _eng._index_pool(pool, jnp.asarray(keep_idx)))
    kp = jnp.asarray(keep_idx)
    take = lambda x: x[:, :, kp]
    if pre.n == spec.n:
        metrics_k = jax.tree.map(take, pre_out.metrics)
        dead_k = take(pre_out.deadlocked)
        start_k = take(pre_out.start_t)
        end_k = take(pre_out.end_t)
        events_k = take(pre_out.events)
    else:
        suf = eng.fan_window_grid(scenarios, sub_pool, spec, goal,
                                  lo=pre.n, width=spec.n - pre.n)
        cat = lambda a, b: jnp.concatenate([take(a), b], axis=1)
        metrics_k = jax.tree.map(cat, pre_out.metrics, suf.metrics)
        dead_k = cat(pre_out.deadlocked, suf.deadlocked)
        start_k = cat(pre_out.start_t, suf.start_t)
        end_k = cat(pre_out.end_t, suf.end_t)
        events_k = cat(pre_out.events, suf.events)
    # Re-select over the concatenated (S, F, Pk) members in the SAME
    # jitted selection the sharded streamer uses (bitwise contract:
    # fan_select_jit on concatenated metrics == in-jit fan_select).
    flat = jax.tree.map(
        lambda x: x.reshape((S * spec.n * Pk,) + x.shape[3:]), metrics_k)
    member, costs, best, ci, width = _eng.fan_select_jit(
        goal, flat, dead_k.reshape(-1), spec.n, Pk)
    out = _eng.FanOutcome(
        start_t=start_k, end_t=end_k, metrics=metrics_k,
        deadlocked=dead_k, events=events_k, result=None,
        member_costs=member, costs=costs, best=best,
        cost_ci=ci, fan_width=width)
    info = PruneInfo(
        keep=keep_idx,
        best=keep_idx[fetch(out.best)],
        rate=1.0 - Pk / P,
        pre_members=pre_members,
        members=S * (pre.n * P + (spec.n - pre.n) * Pk),
        members_full=S * spec.n * P,
    )
    return out, info
