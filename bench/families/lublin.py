"""Rigid jobs from the Lublin–Feitelson model's batch class: a copy of
the program's ``cluster.workload.lublin_trace``, same RNG call order.

The configuration gives ``total_nodes`` and ``n_jobs``; its ``trace``
group gives ``load``, ``accuracy``, ``max_walltime``, the model's
``size``, ``runtime`` and ``arrival`` parameters and, optionally,
``shuffle_group``: a seed reorders jobs within consecutive runs of that
many jobs (the whole stream if absent)."""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from bench.gen import Trace, as_trace


def lublin_trace(rng: np.random.Generator, n_jobs: int, total_nodes: int,
                 load: float, accuracy: Sequence[float],
                 max_walltime: float, size: Dict[str, float],
                 runtime: Dict[str, float],
                 arrival: Dict[str, float]) -> Trace:
    """Size: one node with probability ``p_serial``, else log2(size)
    uniform on [ulow, umed] with probability ``uprob`` and on [umed,
    uhi] otherwise (uhi = log2 of the cluster, umed = uhi −
    ``umed_below_uhi``), a power of two with probability ``p_pow2``.
    ln(runtime): Gamma(a1, b1) with probability pa·log2(size) + pb,
    else Gamma(a2, b2), within [1 s, ``max_walltime``].  ln(gap):
    Gamma(a, b), the gaps scaled to offer ``load`` of the cluster's
    node-seconds.  Estimate: runtime / U(``accuracy``), capped at
    ``max_walltime``."""
    n = int(n_jobs)
    serial, stage, u, pow2, cls = (rng.random(n) for _ in range(5))
    g1 = rng.gamma(runtime["a1"], runtime["b1"], n)
    g2 = rng.gamma(runtime["a2"], runtime["b2"], n)
    acc = rng.uniform(accuracy[0], accuracy[1], n)
    gaps = np.exp(rng.gamma(arrival["a"], arrival["b"], n))

    uhi = np.log2(total_nodes)
    umed = uhi - size["umed_below_uhi"]
    x = np.where(stage < size["uprob"],
                 size["ulow"] + u * (umed - size["ulow"]),
                 umed + u * (uhi - umed))
    nodes = np.where(pow2 < size["p_pow2"], 2.0 ** np.rint(x),
                     np.rint(2.0 ** x))
    nodes = np.where(serial < size["p_serial"], 1.0, nodes)
    nodes = np.clip(nodes, 1, total_nodes).astype(np.int64)
    p_short = np.clip(runtime["pa"] * np.log2(nodes) + runtime["pb"],
                      0.0, 1.0)
    true = np.clip(np.exp(np.where(cls < p_short, g1, g2)), 1.0,
                   max_walltime)
    est = np.minimum(true / acc, max_walltime)
    submit = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    if n > 1:
        submit *= float((nodes * true).sum()) / (load * total_nodes
                                                 * submit[-1])
    return as_trace(submit, nodes, est, true)


def draw(rng: np.random.Generator, config: dict) -> Trace:
    spec = config["trace"]
    return lublin_trace(rng, config["n_jobs"], config["total_nodes"],
                        spec["load"], spec["accuracy"],
                        spec["max_walltime"], spec["size"],
                        spec["runtime"], spec["arrival"])


def groups(config: dict) -> list:
    n = int(config["n_jobs"])
    g = int(config["trace"].get("shuffle_group", n))
    return [g] * (n // g) + ([n % g] if n % g else [])
