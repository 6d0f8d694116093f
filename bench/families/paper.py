"""The paper's §4.1 four-phase trace: a copy of the program's
``cluster.workload.paper_synthetic_trace``, same RNG call order.

The configuration's ``trace`` group gives ``phases`` (each ``n_jobs``,
``nodes`` and ``walltime`` ranges), ``arrival_gap`` and ``accuracy``.
A seed reorders jobs within each phase."""
from __future__ import annotations

from typing import Sequence

import numpy as np

from bench.gen import Trace, as_trace


def paper_trace(rng: np.random.Generator, phases: Sequence[dict],
                arrival_gap: float, accuracy: Sequence[float]) -> Trace:
    """Per phase ``n_jobs`` jobs with nodes uniform on ``nodes``
    (inclusive) and walltime uniform on ``walltime``; true runtime =
    walltime × U(accuracy), at least 1 s."""
    submit, nodes, est, true = [], [], [], []
    t = 0.0
    for ph in phases:
        lo_n, hi_n = ph["nodes"]
        lo_w, hi_w = ph["walltime"]
        for _ in range(int(ph["n_jobs"])):
            n = int(rng.integers(lo_n, hi_n + 1))
            e = float(rng.uniform(lo_w, hi_w))
            acc = float(rng.uniform(accuracy[0], accuracy[1]))
            submit.append(t)
            nodes.append(n)
            est.append(e)
            true.append(max(1.0, e * acc))
            t += arrival_gap
    return as_trace(submit, nodes, est, true)


def draw(rng: np.random.Generator, config: dict) -> Trace:
    spec = config["trace"]
    return paper_trace(rng, spec["phases"], spec["arrival_gap"],
                       spec["accuracy"])


def groups(config: dict) -> list:
    return [int(ph["n_jobs"]) for ph in config["trace"]["phases"]]
