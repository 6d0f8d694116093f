"""Scheduling policy space.

A policy is a *priority key function*: lower key = scheduled earlier.
The paper's pool (§4.1) is {WFP (ALCF utility), FCFS, SJF}, all with
EASY backfilling, and its design explicitly allows "a pool of candidate
policies ... provided that they exhibit complementary strengths" (§3).

Two representations live here:

* **Integer policy ids** (`WFP` ... `EXPF`, `priority_key`) — the
  original hardcoded 7-row key stack.  Kept verbatim as the *oracle*
  the parametric path is parity-tested against, and as the input type
  of the `pool_array` adapter.

* **`PolicySpec` — the parametric policy space (tentpole).**  Every
  what-if fork carries `(family, theta)`: the priority key is a linear
  contraction of a per-job *feature matrix* (wait, est, nodes, area,
  xfactor, submit) against the fork's θ, plus a family-specific
  nonlinear term (WFP-style power utilities, exponential aging).  The
  7 static policies are **fixed points** of this space (e.g. WFP =
  `-(wait/est)^a · nodes^b` with a=3, b=1) and are constructed so
  their keys are *bit-identical* to the integer-id stack: one-hot
  linear weights select single features exactly, and `_pow`
  special-cases small integer exponents so `x^3` lowers to the same
  `x·x·x` as `lax.integer_pow`.

  This is what unlocks DRAS-style parameter sweeps (one fork per grid
  point, Fan & Lan 2021) and RLScheduler-style learned priority
  scorers (Zhang et al. 2020, a learned θ on the `lin` family) riding
  the same fork axis of the batched drain engine — see DESIGN.md §5.

θ deliberately lives in **stage 1** of the engine (keys + argsort,
outside the Pallas scheduling-pass kernel): key evaluation is
embarrassingly parallel and XLA-fused, and the kernel's working set
stays the six queue fields regardless of pool parameterization.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import JobTable
from repro.core.telemetry import fetch

# Canonical ids — tie-break order is numeric order (paper §4.2).
WFP = 0    # ALCF utility: run job maximizing (wait/est)^3 * nodes
FCFS = 1   # first-come-first-served
SJF = 2    # shortest (estimated) job first
# --- beyond-paper pool extensions ---
SAF = 3    # smallest area (nodes * est) first
LJF = 4    # longest job first
LXF = 5    # largest expansion factor first: (wait + est) / est
EXPF = 6   # exponential aging of wait time

POLICY_NAMES = {
    WFP: "WFP", FCFS: "FCFS", SJF: "SJF",
    SAF: "SAF", LJF: "LJF", LXF: "LXF", EXPF: "EXPF",
}
PAPER_POOL: Sequence[int] = (WFP, FCFS, SJF)
EXTENDED_POOL: Sequence[int] = (WFP, FCFS, SJF, SAF, LJF, LXF, EXPF)

_EST_FLOOR = 1.0  # seconds; guards division by tiny estimates


def priority_key(jobs: JobTable, now: jax.Array, policy_id) -> jax.Array:
    """Per-job priority keys (lower = run first) for integer ``policy_id``.

    The pre-parametric 7-row key stack, kept bit-for-bit as the oracle
    `tests/test_policyspec.py` asserts the `PolicySpec` fixed points
    against.  Utility policies (WFP, LXF, EXPF) are re-evaluated at
    every scheduling instance with the current wait time, exactly as a
    live utility scheduler recomputes job scores each cycle.

    Stable argsort + slot-ids-in-submission-order means ties fall back
    to FCFS order, the conventional secondary key.
    """
    wait = jnp.maximum(now - jobs.submit_t, 0.0)
    est = jnp.maximum(jobs.est_runtime, _EST_FLOOR)
    nodes = jobs.nodes.astype(jnp.float32)

    # Scores where higher = more deserving; keys are negated scores.
    wfp_score = (wait / est) ** 3 * nodes
    lxf_score = (wait + est) / est
    expf_score = jnp.expm1(jnp.minimum(wait / 3600.0, 30.0))  # hourly aging

    keys = jnp.stack([
        -wfp_score,            # WFP
        jobs.submit_t,         # FCFS
        est,                   # SJF
        nodes * est,           # SAF
        -est,                  # LJF
        -lxf_score,            # LXF
        -expf_score,           # EXPF
    ])
    return keys[policy_id]


def policy_name(policy_id: int) -> str:
    return POLICY_NAMES[int(policy_id)]


# ======================================================================
# Parametric policy space: PolicySpec = (family, theta)
# ======================================================================

#: Feature-matrix columns (order = θ linear-weight layout).
FEATURES: Tuple[str, ...] = ("wait", "est", "nodes", "area", "xfactor",
                             "submit")
N_FEATURES = len(FEATURES)

# θ layout: [0:N_FEATURES] linear weights over FEATURES, then the
# family-specific nonlinear parameters.
TH_A = N_FEATURES        # WFP family: exponent on wait/est
TH_B = N_FEATURES + 1    # WFP family: exponent on nodes
TH_TAU = N_FEATURES + 2  # WFP/EXP families: aging timescale (seconds)
N_THETA = N_FEATURES + 3

AGING_CAP = 30.0  # cap on wait/tau before exp() — matches legacy EXPF

# Families.
FAM_LIN = 0   # key = Φ·θ_lin                     (FCFS/SJF/SAF/LJF/LXF)
FAM_WFP = 1   # key = Φ·θ_lin - (wait/est)^a · nodes^b · e^min(wait/τ,cap)
FAM_EXP = 2   # key = Φ·θ_lin - expm1(min(wait/τ, cap))        (EXPF)

FAMILY_NAMES = {FAM_LIN: "lin", FAM_WFP: "wfp", FAM_EXP: "expf"}

#: Per-family nonlinear parameters exposed to the sweep grammar,
#: with their fixed-point defaults.
FAMILY_PARAMS: Dict[int, Dict[str, Tuple[int, float]]] = {
    FAM_LIN: {},
    FAM_WFP: {"a": (TH_A, 3.0), "b": (TH_B, 1.0), "tau": (TH_TAU, np.inf)},
    FAM_EXP: {"tau": (TH_TAU, 3600.0)},
}


class PolicySpec(NamedTuple):
    """One policy fork (or a stacked pool of k forks) in parameter space.

    ``family`` — i32, scalar (one fork) or (k,) (a pool).
    ``theta``  — f32, (N_THETA,) or (k, N_THETA): linear feature
    weights followed by the family's nonlinear parameters.

    A PyTree, so a pool rides jit/vmap/sharding like any array: the
    fork axis of the batched drain engine IS the leading axis of both
    leaves, and ``sharded_whatif`` partitions θ together with it.
    """
    family: jax.Array
    theta: jax.Array


def job_features(jobs: JobTable, now: jax.Array) -> jax.Array:
    """The (J, N_FEATURES) feature matrix Φ every priority key is a
    function of.  Columns follow ``FEATURES``; ``est`` is floored at
    ``_EST_FLOOR`` exactly as the legacy key stack does."""
    wait = jnp.maximum(now - jobs.submit_t, 0.0)
    est = jnp.maximum(jobs.est_runtime, _EST_FLOOR)
    nodes = jobs.nodes.astype(jnp.float32)
    return jnp.stack([
        wait,
        est,
        nodes,
        nodes * est,          # area
        (wait + est) / est,   # xfactor (expansion factor)
        jobs.submit_t,
    ], axis=-1)


def _pow(x: jax.Array, p: jax.Array) -> jax.Array:
    """x^p with exact products for the small integer exponents the
    static fixed points use: `x*x*x` is bit-identical to
    `lax.integer_pow(x, 3)` (same association under exponentiation by
    squaring), while `jnp.power` would lower to exp(p·log x) and drift
    in the last ulp.  x must be >= 0 (ratios and node counts are)."""
    return jnp.where(p == 1.0, x,
           jnp.where(p == 2.0, x * x,
           jnp.where(p == 3.0, x * x * x,
                     jnp.power(x, p))))


def priority_key_spec(jobs: JobTable, now: jax.Array,
                      spec: PolicySpec) -> jax.Array:
    """Per-job priority keys (J,) for ONE parametric fork.

    key = Φ·θ_lin + nonlinear(family, θ): the linear contraction is
    shared by every family; WFP/EXP add their nonlinear utility
    (negated — higher utility = lower key = runs first).
    """
    feats = job_features(jobs, now)                     # (J, F)
    wait, est, nodes = feats[:, 0], feats[:, 1], feats[:, 2]

    lin = feats @ spec.theta[:N_FEATURES]               # (J,)

    a, b = spec.theta[TH_A], spec.theta[TH_B]
    tau = spec.theta[TH_TAU]
    aged = jnp.minimum(wait / tau, AGING_CAP)           # 0 when tau=inf
    wfp_nl = -(_pow(wait / est, a) * _pow(nodes, b) * jnp.exp(aged))
    exp_nl = -jnp.expm1(aged)

    nl = jnp.where(spec.family == FAM_WFP, wfp_nl,
         jnp.where(spec.family == FAM_EXP, exp_nl, 0.0))
    return lin + nl


def batched_priority_keys(jobs: JobTable, now: jax.Array,
                          pool_spec: PolicySpec) -> jax.Array:
    """(k, J) priority keys for a whole pool against ONE shared
    snapshot — the first scheduling pass of a decision cycle, before
    fork states diverge.  (Mid-drain, the engine vmaps
    ``priority_key_spec`` over per-fork states instead.)"""
    return jax.vmap(priority_key_spec, in_axes=(None, None, 0))(
        jobs, now, pool_spec)


# ----------------------------------------------------------------------
# Time-invariance: which forks' keys never depend on ``now``?
# ----------------------------------------------------------------------

#: Legacy ids whose key is a pure function of static job fields
#: (submit_t / est / nodes) — WFP, LXF and EXPF re-score with the
#: current wait time every cycle and are excluded.
STATIC_KEY_IDS = frozenset({FCFS, SJF, SAF, LJF})

_WAIT_COL = FEATURES.index("wait")
_XF_COL = FEATURES.index("xfactor")


#: ``time_invariant_mask`` memo: id(leaf)-tuple -> mask.  The
#: ``np.asarray`` over concrete pool leaves is a device sync PER
#: DECISION CYCLE on the hot path (``engine.plan`` runs it every call);
#: the pool arrays are immutable device buffers, so identity is a
#: sound cache key as long as entries are evicted when the leaves die
#: (``weakref.finalize`` below — never on raw id reuse).
_TI_MASK_CACHE: dict = {}


def time_invariant_mask(pool) -> np.ndarray:
    """Host-side (k,) bool: forks whose priority keys are independent
    of the clock, so their argsort can be hoisted OUT of the per-event
    loop (DESIGN.md §7).

    A fork qualifies iff its key is a function of static job fields
    only (``submit_t``/``est``/``nodes``/``area``):

    * ``lin``-family specs with zero weight on the ``wait`` and
      ``xfactor`` feature columns (FCFS, SJF, SAF, LJF and most learned
      scorers sit here);
    * legacy ids in ``STATIC_KEY_IDS``.

    ``wfp``/``expf`` family forks always re-score with the current wait
    time, so they stay time-varying regardless of θ.  The mask is a
    *host* computation over concrete pool arrays — it partitions the
    fork axis statically, before jit — memoized per pool identity so
    the repeated device->host sync disappears from the cycle loop."""
    import weakref
    leaves = ((pool.family, pool.theta) if isinstance(pool, PolicySpec)
              else (pool,))
    key = tuple(id(leaf) for leaf in leaves)
    hit = _TI_MASK_CACHE.get(key)
    if hit is not None:
        return hit
    if isinstance(pool, PolicySpec):
        fam = fetch(pool.family).reshape(-1)
        th = fetch(pool.theta).reshape(fam.shape[0], -1)
        mask = ((fam == FAM_LIN)
                & (th[:, _WAIT_COL] == 0.0)
                & (th[:, _XF_COL] == 0.0))
    else:
        ids = fetch(pool).reshape(-1)
        mask = np.isin(ids, sorted(STATIC_KEY_IDS))
    mask.setflags(write=False)
    try:
        for leaf in leaves:
            weakref.finalize(leaf, _TI_MASK_CACHE.pop, key, None)
    except TypeError:
        return mask          # un-weakref-able leaf: serve uncached
    _TI_MASK_CACHE[key] = mask
    return mask


# ----------------------------------------------------------------------
# Spec constructors: families and the 7 static fixed points.
# ----------------------------------------------------------------------

def _base_theta() -> np.ndarray:
    th = np.zeros((N_THETA,), dtype=np.float32)
    th[TH_TAU] = np.inf  # aged = wait/inf = 0: aging off by default
    return th


def linear_spec(**weights: float) -> PolicySpec:
    """`lin` family: key = Σ w_f · feature_f.  Keyword names index
    ``FEATURES`` (e.g. ``linear_spec(est=1.0)`` is SJF).  A learned
    priority scorer (RLScheduler-style) is just a trained θ here."""
    th = _base_theta()
    for name, w in weights.items():
        if name not in FEATURES:
            raise ValueError(f"unknown feature {name!r}; have {FEATURES}")
        th[FEATURES.index(name)] = w
    return PolicySpec(jnp.int32(FAM_LIN), jnp.asarray(th))


def wfp_spec(a: float = 3.0, b: float = 1.0,
             tau: float = np.inf) -> PolicySpec:
    """`wfp` family: key = -(wait/est)^a · nodes^b · e^min(wait/τ, cap).
    Defaults (a=3, b=1, τ=∞) are the paper's WFP exactly; sweeping
    (a, τ) is the DRAS-style dynamic parameterization axis."""
    if tau <= 0:
        raise ValueError(f"wfp tau must be > 0, got {tau}")
    th = _base_theta()
    th[TH_A], th[TH_B], th[TH_TAU] = a, b, tau
    return PolicySpec(jnp.int32(FAM_WFP), jnp.asarray(th))


def exp_spec(tau: float = 3600.0) -> PolicySpec:
    """`expf` family: key = -expm1(min(wait/τ, cap)).  τ=3600 is the
    legacy EXPF (hourly aging)."""
    if tau <= 0:
        raise ValueError(f"expf tau must be > 0, got {tau}")
    th = _base_theta()
    th[TH_TAU] = tau
    return PolicySpec(jnp.int32(FAM_EXP), jnp.asarray(th))


#: The 7 static policies as fixed points of the parametric space.
_STATIC_SPECS = {
    WFP: lambda: wfp_spec(),
    FCFS: lambda: linear_spec(submit=1.0),
    SJF: lambda: linear_spec(est=1.0),
    SAF: lambda: linear_spec(area=1.0),
    LJF: lambda: linear_spec(est=-1.0),
    LXF: lambda: linear_spec(xfactor=-1.0),
    EXPF: lambda: exp_spec(),
}


def static_spec(policy_id: int) -> PolicySpec:
    """The parametric fixed point of one integer policy id.  Its keys
    are bit-identical to ``priority_key(jobs, now, policy_id)``."""
    return _STATIC_SPECS[int(policy_id)]()


def stack_specs(specs: Sequence[PolicySpec]) -> PolicySpec:
    """Stack scalar specs into a pool with a leading fork axis."""
    if not specs:
        raise ValueError("empty policy pool")
    return PolicySpec(
        family=jnp.stack([s.family for s in specs]),
        theta=jnp.stack([s.theta for s in specs]),
    )


def spec_rows(pool: PolicySpec) -> List[PolicySpec]:
    """The scalar per-fork specs of a stacked pool (host-side)."""
    fam = np.asarray(pool.family)
    th = np.asarray(pool.theta)
    return [PolicySpec(jnp.int32(int(fam[i])), jnp.asarray(th[i]))
            for i in range(fam.shape[0])]


def describe_spec(family: int, theta: np.ndarray) -> str:
    """Human-readable name for one fork: canonical static names where
    θ sits exactly on a fixed point, else ``family[k=v,...]``."""
    family = int(family)
    theta = np.asarray(theta)
    for pid, ctor in _STATIC_SPECS.items():
        ref = ctor()
        if int(ref.family) == family and np.array_equal(
                np.asarray(ref.theta), theta.astype(np.float32)):
            return POLICY_NAMES[pid]
    parts = []
    if family == FAM_LIN:
        for i, fname in enumerate(FEATURES):
            if theta[i] != 0.0:
                parts.append(f"{fname}={theta[i]:g}")
    else:
        for pname, (idx, default) in FAMILY_PARAMS[family].items():
            if theta[idx] != np.float32(default):
                parts.append(f"{pname}={theta[idx]:g}")
    base = FAMILY_NAMES[family]
    return f"{base}[{','.join(parts)}]" if parts else base


# ----------------------------------------------------------------------
# PolicyPool: the user-facing pool (specs + display names) + grammar.
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class PolicyPool:
    """A candidate pool: stacked ``PolicySpec`` + per-fork names.

    Pool *position* is tie-break priority (``select_policy`` is an
    argmin with first-occurrence wins), exactly as with the legacy id
    arrays.  ``spec`` is what flows into the engine; ``names`` feed
    telemetry/scoring reports.
    """
    spec: PolicySpec
    names: Tuple[str, ...]

    def __post_init__(self) -> None:
        if len(self.names) != self.spec.family.shape[0]:
            raise ValueError(
                f"{len(self.names)} names for "
                f"{self.spec.family.shape[0]} forks")

    def __len__(self) -> int:
        return self.spec.family.shape[0]

    @property
    def size(self) -> int:
        return len(self)

    def __add__(self, other: "PolicyPool") -> "PolicyPool":
        return PolicyPool(
            spec=PolicySpec(
                jnp.concatenate([self.spec.family, other.spec.family]),
                jnp.concatenate([self.spec.theta, other.spec.theta])),
            names=self.names + other.names)

    def fork(self, p: int) -> PolicySpec:
        """Fork p as a scalar ``PolicySpec`` — e.g. to baseline one
        pool member through the emulator's static mode."""
        return PolicySpec(self.spec.family[p], self.spec.theta[p])

    @classmethod
    def from_ids(cls, ids: Sequence[int]) -> "PolicyPool":
        """Static fixed points for a legacy id pool (caller's order =
        tie-break order, as with ``pool_array``)."""
        ids = [int(i) for i in np.asarray(list(ids))]
        return cls(spec=stack_specs([static_spec(i) for i in ids]),
                   names=tuple(POLICY_NAMES[i] for i in ids))

    @classmethod
    def from_specs(cls, specs: Sequence[PolicySpec],
                   names: Sequence[str] | None = None) -> "PolicyPool":
        pool = stack_specs(list(specs))
        if names is None:
            names = [describe_spec(s.family, np.asarray(s.theta))
                     for s in specs]
        return cls(spec=pool, names=tuple(names))


def theta_pool(family: int, thetas: np.ndarray,
               names: Sequence[str] | None = None) -> PolicyPool:
    """Pool construction from trained θ: (N, N_THETA) rows of ONE
    family become a PolicyPool riding the fork axis — this is how the
    ``learn`` trainer evaluates a whole candidate generation as one
    replay grid, and how a checkpointed θ deploys."""
    th = np.asarray(thetas, np.float32)
    if th.ndim == 1:
        th = th[None, :]
    if th.ndim != 2 or th.shape[1] != N_THETA:
        raise ValueError(f"thetas must be (N, {N_THETA}), got {th.shape}")
    if int(family) not in FAMILY_NAMES:
        raise ValueError(f"unknown family {family}; have {FAMILY_NAMES}")
    spec = PolicySpec(jnp.full((th.shape[0],), int(family), jnp.int32),
                      jnp.asarray(th))
    if names is None:
        names = [describe_spec(int(family), th[i])
                 for i in range(th.shape[0])]
    return PolicyPool(spec=spec, names=tuple(names))


_STATIC_BY_NAME = {POLICY_NAMES[i].lower(): i for i in EXTENDED_POOL}
_FAMILY_BY_NAME = {v: k for k, v in FAMILY_NAMES.items()}


def _parse_values(text: str) -> List[float]:
    """``v`` -> [v];  ``lo..hixN`` -> linspace(lo, hi, N)."""
    if ".." in text:
        lo_s, rest = text.split("..", 1)
        if "x" not in rest:
            raise ValueError(
                f"sweep {text!r} must be 'lo..hixN' (e.g. 1..5x5)")
        hi_s, n_s = rest.rsplit("x", 1)
        n = int(n_s)
        if n < 2:
            raise ValueError(f"sweep {text!r} needs >= 2 points")
        return [float(v) for v in np.linspace(float(lo_s), float(hi_s), n)]
    return [float(text)]


def parse_pool(grammar: str) -> PolicyPool:
    """Expand a pool grammar into a PolicyPool — one fork per grid point.

    Grammar: comma-separated terms, each
    ``name[:param=value | :param=lo..hixN]...`` where multiple swept
    params take their cartesian product (rightmost fastest):

      ``paper``                      -> WFP, FCFS, SJF (statics)
      ``extended``                   -> all 7 statics
      ``wfp,fcfs,sjf``               -> 3 static fixed points
      ``wfp:a=2``                    -> one parametric WFP fork
      ``wfp:a=1..5x5:tau=600..7200x5`` -> 25-point DRAS-style grid
      ``expf:tau=600``               -> fast-aging EXPF
      ``lin:est=1:wait=-0.01``       -> linear scorer over features
      ``trained:<ckpt-dir>``         -> learned θ from a checkpoint
                                        (``learn.train``); statics can
                                        ride alongside as a safety
                                        floor: ``trained:ckpt,paper``

    Term order is tie-break priority, matching ``pool_array``.
    """
    specs: List[PolicySpec] = []
    names: List[str] = []
    for term in (t.strip() for t in grammar.split(",")):
        if not term:
            continue
        if term.lower().startswith("trained:"):
            # Everything after the prefix is a filesystem path — keep
            # it out of the ":"-assignment split below.
            path = term[len("trained:"):].strip()
            if not path:
                raise ValueError(
                    "trained: needs a checkpoint dir, e.g. "
                    "trained:checkpoints/policy")
            from repro.learn.trainer import load_trained_pool  # lazy: learn imports core
            trained = load_trained_pool(path)
            specs.extend(spec_rows(trained.spec))
            names.extend(trained.names)
            continue
        head, *assigns = term.split(":")
        name = head.strip().lower()
        if not assigns:
            if name == "paper":
                ids = PAPER_POOL
            elif name == "extended":
                ids = EXTENDED_POOL
            elif name in _STATIC_BY_NAME:
                ids = (_STATIC_BY_NAME[name],)
            elif name in _FAMILY_BY_NAME:
                # bare family name -> its default point
                fam = _FAMILY_BY_NAME[name]
                spec = {FAM_LIN: linear_spec, FAM_WFP: wfp_spec,
                        FAM_EXP: exp_spec}[fam]()
                specs.append(spec)
                names.append(describe_spec(spec.family,
                                           np.asarray(spec.theta)))
                continue
            else:
                raise ValueError(
                    f"unknown pool term {head!r}; statics: "
                    f"{sorted(_STATIC_BY_NAME)}, families: "
                    f"{sorted(_FAMILY_BY_NAME)}")
            for pid in ids:
                specs.append(static_spec(pid))
                names.append(POLICY_NAMES[pid])
            continue

        # parametric term: resolve the family
        if name in _FAMILY_BY_NAME:
            fam = _FAMILY_BY_NAME[name]
        else:
            raise ValueError(
                f"term {head!r} takes no parameters; parametric "
                f"families: {sorted(_FAMILY_BY_NAME)}")

        keys: List[str] = []
        grids: List[List[float]] = []
        for assign in assigns:
            if "=" not in assign:
                raise ValueError(f"bad assignment {assign!r} in {term!r}")
            key, val = assign.split("=", 1)
            key = key.strip().lower()
            if fam == FAM_LIN:
                if key not in FEATURES:
                    raise ValueError(
                        f"lin weights index features {FEATURES}, "
                        f"got {key!r}")
            elif key not in FAMILY_PARAMS[fam]:
                raise ValueError(
                    f"{FAMILY_NAMES[fam]!r} params are "
                    f"{sorted(FAMILY_PARAMS[fam])}, got {key!r}")
            keys.append(key)
            grids.append(_parse_values(val.strip()))

        for combo in itertools.product(*grids):
            kw = dict(zip(keys, combo))
            if fam == FAM_LIN:
                spec = linear_spec(**kw)
            elif fam == FAM_WFP:
                spec = wfp_spec(**kw)
            else:
                spec = exp_spec(**kw)
            specs.append(spec)
            label = ",".join(f"{k}={v:g}" for k, v in zip(keys, combo))
            names.append(f"{FAMILY_NAMES[fam]}[{label}]")
    return PolicyPool.from_specs(specs, names)


PoolLike = Union["PolicyPool", PolicySpec, str, jax.Array,
                 np.ndarray, Sequence[int]]


def normalize_pool(pool: PoolLike) -> PolicyPool:
    """Coerce any pool representation to a PolicyPool:

    * ``PolicyPool``        — returned as is;
    * ``PolicySpec`` (k,)   — named via ``describe_spec``;
    * ``str``               — sweep grammar (``parse_pool``);
    * id array / sequence   — static fixed points (``from_ids``).
    """
    if isinstance(pool, PolicyPool):
        return pool
    if isinstance(pool, PolicySpec):
        if pool.family.ndim == 0:  # scalar fork -> k=1 pool
            pool = PolicySpec(pool.family.reshape(1),
                              pool.theta.reshape(1, -1))
        fam = np.asarray(pool.family)
        th = np.asarray(pool.theta)
        return PolicyPool(
            spec=pool,
            names=tuple(describe_spec(fam[i], th[i])
                        for i in range(fam.shape[0])))
    if isinstance(pool, str):
        return parse_pool(pool)
    return PolicyPool.from_ids(pool)
