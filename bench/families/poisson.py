"""Poisson arrivals with heavy-tailed walltimes: a copy of the program's
``cluster.workload.poisson_trace`` with ``heavy_tail=True``, same RNG
call order.

The configuration gives ``total_nodes`` and ``n_jobs``; its ``trace``
group gives ``mean_gap``, ``node_range``, ``walltime_range``,
``accuracy`` and, optionally, ``shuffle_group``: a seed reorders jobs
within consecutive runs of that many jobs (the whole stream if
absent)."""
from __future__ import annotations

from typing import Sequence

import numpy as np

from bench.gen import Trace, as_trace


def poisson_trace(rng: np.random.Generator, n_jobs: int, total_nodes: int,
                  mean_gap: float, node_range: Sequence[int],
                  walltime_range: Sequence[float],
                  accuracy: Sequence[float]) -> Trace:
    """Poisson arrivals, nodes uniform on ``node_range`` (capped at the
    cluster), walltime lognormal with its median at the geometric mean
    of ``walltime_range`` and clipped to it; true runtime = walltime ×
    U(accuracy), at least 1 s."""
    lo_w, hi_w = walltime_range
    mu = np.log(np.sqrt(lo_w * hi_w))
    sigma = np.log(hi_w / lo_w) / 4.0
    submit, nodes, est, true = [], [], [], []
    t = 0.0
    for _ in range(int(n_jobs)):
        t += float(rng.exponential(mean_gap))
        n = int(rng.integers(node_range[0],
                             min(node_range[1], total_nodes) + 1))
        e = float(np.clip(rng.lognormal(mu, sigma), lo_w, hi_w))
        acc = float(rng.uniform(accuracy[0], accuracy[1]))
        submit.append(t)
        nodes.append(n)
        est.append(e)
        true.append(max(1.0, e * acc))
    return as_trace(submit, nodes, est, true)


def draw(rng: np.random.Generator, config: dict) -> Trace:
    spec = config["trace"]
    return poisson_trace(rng, config["n_jobs"], config["total_nodes"],
                         spec["mean_gap"], spec["node_range"],
                         spec["walltime_range"], spec["accuracy"])


def groups(config: dict) -> list:
    n = int(config["n_jobs"])
    g = int(config["trace"].get("shuffle_group", n))
    return [g] * (n // g) + ([n % g] if n % g else [])
