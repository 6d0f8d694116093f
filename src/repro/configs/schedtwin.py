"""The paper's own configuration (§4.1): cluster, policy pool, score."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from repro.core.engine import DrainEngine
from repro.core.objective import Objective, resolve_goal
from repro.core.policies import (EXTENDED_POOL, PAPER_POOL, PolicyPool,
                                 normalize_pool)
from repro.core.scoring import ScoreWeights

#: DRAS-style 25-point sweep (5x5 grid over the WFP exponent and the
#: aging timescale) riding alongside the 7 static specs -> k=32 forks
#: in ONE batched drain.  Also the acceptance benchmark's pool
#: (benchmarks/overhead.py "dras_sweep") and a ``--pool`` value for
#: ``repro.launch.twin_loop``.
DRAS_SWEEP_POOL = "extended,wfp:a=1..5x5:tau=600..7200x5"


@dataclasses.dataclass(frozen=True)
class TwinConfig:
    total_nodes: int = 32             # 32-node PBS cluster (CloudLab)
    max_jobs: int = 256
    # Candidate pool: a tuple of legacy policy ids (lifted to their
    # parametric fixed points) or a sweep-grammar string such as
    # ``"paper"`` or ``DRAS_SWEEP_POOL`` (see policies.parse_pool).
    pool: Union[str, Tuple[int, ...]] = tuple(PAPER_POOL)  # WFP, FCFS, SJF
    # The administrator-configured optimization goal (§3.4; DESIGN.md
    # §8): an objective-grammar string ("score", "avg_wait",
    # "min:avg_wait@util>=0.85", ...) or an ``objective.Objective``.
    # "score" is the paper's 4-term score, bit-identical to the
    # pre-objective ScoreWeights path.
    objective: Union[str, Objective] = "score"
    # DEPRECATED: legacy goal spelling.  When set, it lifts to the
    # bit-identical paper-score objective (with a DeprecationWarning)
    # and must not be combined with a non-default ``objective``.
    weights: Optional[ScoreWeights] = None
    ensemble: int = 1                 # >1 -> uncertainty ensemble (beyond)
    ensemble_noise: float = 0.3
    trace_seed: int = 0
    accuracy: Tuple[float, float] = (0.5, 1.0)     # true/estimated runtime
    # What-if engine: scheduling-pass backend ("reference" = pure-JAX
    # oracle, "pallas" = the TPU kernel, "auto" = reference on CPU /
    # pallas on TPU — interpret-mode pallas is ~2.3x slower than
    # reference on CPU, BENCH_overhead.json) and Pallas interpret
    # override (None resolves from the platform: the compiled kernel
    # on TPU, interpret mode on CPU).
    backend: str = "auto"
    interpret: Optional[bool] = None

    def make_engine(self) -> DrainEngine:
        """The policy-batched drain engine this config selects."""
        return DrainEngine(backend=self.backend, interpret=self.interpret)

    def make_pool(self) -> PolicyPool:
        """The parametric candidate pool this config describes."""
        return normalize_pool(self.pool)

    def make_objective(self) -> Objective:
        """The resolved optimization goal (legacy ``weights`` lifted)."""
        if self.weights is not None and self.objective == "score":
            return resolve_goal(None, self.weights)   # legacy spelling
        return resolve_goal(self.objective, self.weights)


PAPER_TWIN = TwinConfig()
EXTENDED_TWIN = TwinConfig(pool=tuple(EXTENDED_POOL))
PALLAS_TWIN = TwinConfig(backend="pallas")
SWEEP_TWIN = TwinConfig(pool=DRAS_SWEEP_POOL)


@dataclasses.dataclass(frozen=True)
class ReplayGridConfig:
    """A (scenario × policy) baseline grid for the replay engine
    (DESIGN.md §6): S traces of one workload family × the candidate
    pool, evaluated as ONE device computation
    (``engine.replay_grid``).  Used by ``twin_loop --replay-grid`` and
    ``benchmarks/baseline_sweep.py``."""

    scenarios: int = 8
    trace: str = "poisson"            # poisson | bursty | paper
    n_jobs: int = 48
    total_nodes: int = 32
    mean_gap: float = 8.0
    node_range: Tuple[int, int] = (1, 16)
    walltime_range: Tuple[float, float] = (30.0, 900.0)
    pool: Union[str, Tuple[int, ...]] = tuple(EXTENDED_POOL)   # P=7
    # Goal for the grid's per-scenario selection (``ReplayOutcome.best``)
    objective: Union[str, Objective] = "score"
    seed: int = 0
    backend: str = "auto"
    interpret: Optional[bool] = None

    def make_engine(self) -> DrainEngine:
        return DrainEngine(backend=self.backend, interpret=self.interpret)

    def make_pool(self) -> PolicyPool:
        return normalize_pool(self.pool)

    def make_objective(self) -> Objective:
        return resolve_goal(self.objective)

    def make_traces(self):
        """One trace per scenario: the same family, consecutive seeds —
        the 'many what-if futures' axis."""
        from repro.cluster.workload import (bursty_trace,
                                            paper_synthetic_trace,
                                            poisson_trace)
        traces = []
        for s in range(self.scenarios):
            seed = self.seed + s
            if self.trace == "paper":
                traces.append(paper_synthetic_trace(seed=seed))
            elif self.trace == "bursty":
                traces.append(bursty_trace(
                    self.n_jobs, self.total_nodes, self.mean_gap,
                    self.node_range, self.walltime_range, seed=seed))
            elif self.trace == "poisson":
                traces.append(poisson_trace(
                    self.n_jobs, self.total_nodes, self.mean_gap,
                    self.node_range, self.walltime_range, seed=seed))
            else:
                raise ValueError(f"unknown trace family {self.trace!r}")
        return traces

    def make_scenarios(self):
        """The stacked, padded ``workload.ScenarioSet``."""
        from repro.cluster.workload import stack_scenarios
        return stack_scenarios(self.make_traces(), self.total_nodes)


REPLAY_GRID = ReplayGridConfig()
