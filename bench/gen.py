"""Trace generation for the benchmark: the yardstick's own copy.

A trace family is a file of its own, ``bench/families/<family>.py``,
found by the name a configuration's ``trace`` group gives
(``spec.family``).  It exports ``draw(rng, config) -> Trace``, one
stream of the configuration drawn from a numpy ``Generator``, and
``groups(config) -> list``, the job counts of the stream's consecutive
groups within which a seed may reorder.  The families copy the
program's generators in ``cluster/workload.py`` with the same RNG call
order, so a seed gives the same jobs here as there, and a change to the
program's generator cannot move the benchmark.

A trace is plain numpy (``Trace``): the reference scheduler reads it
as it is, and the drivers turn it into the program's ``JobSpec``s
(``jobspecs``).  A configuration that names a ``draws_seed`` draws its
jobs once, and each trace reorders them (``make_trace``).  A cell's
traces are one fixed set whose order the run's seed draws
(``scenario_traces``).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np


class Trace(NamedTuple):
    """One job stream, slot j = job j in submission order."""
    submit_t: np.ndarray      # f64 seconds
    nodes: np.ndarray         # i64
    est_runtime: np.ndarray   # f64 user walltime estimate
    true_runtime: np.ndarray  # f64 ground truth (never shown to the twin)

    def __len__(self) -> int:
        return int(self.submit_t.shape[0])


def as_trace(submit, nodes, est, true) -> Trace:
    return Trace(np.asarray(submit, np.float64), np.asarray(nodes, np.int64),
                 np.asarray(est, np.float64), np.asarray(true, np.float64))


def jobspecs(trace: Trace):
    """The program's ``JobSpec``s of ``trace``: all the program gets."""
    from repro.cluster.workload import JobSpec
    return [JobSpec(j, float(trace.submit_t[j]), int(trace.nodes[j]),
                    float(trace.est_runtime[j]),
                    float(trace.true_runtime[j]))
            for j in range(len(trace))]


def draw(family, config: dict, seed: int) -> Trace:
    """One trace of ``config``'s stream drawn by ``family`` from
    ``seed``, as the program's generator draws it."""
    return family.draw(np.random.default_rng(seed), config)


def reorder(base: Trace, sizes: Sequence[int],
            rng: np.random.Generator) -> Trace:
    """``base`` with its jobs (nodes, estimate, true runtime) and the
    gaps between its arrivals shuffled within each group: the same
    jobs, each group arriving over the same stretch, in another
    order."""
    starts = np.cumsum([0] + list(sizes[:-1]))
    order = np.concatenate([lo + rng.permutation(n)
                            for lo, n in zip(starts, sizes)])
    gaps = np.diff(base.submit_t, prepend=base.submit_t[0])
    new_gaps = gaps.copy()
    for lo, n in zip(starts, sizes):
        at = np.arange(max(lo, 1), lo + n)   # gaps[0] is 0: the first arrival
        new_gaps[at] = gaps[rng.permutation(at)]
    return Trace(base.submit_t[0] + np.cumsum(new_gaps),
                 base.nodes[order], base.est_runtime[order],
                 base.true_runtime[order])


def make_trace(family, config: dict, seed: int) -> Trace:
    """One trace of ``config``'s stream from ``seed``, a whole number of
    any size.  Where the ``trace`` group names a ``draws_seed``, every
    seed gets the jobs and gaps drawn once from that seed, in an order
    of its own (``reorder``), so that seeds change the order and not the
    amount of work."""
    draws = config["trace"].get("draws_seed")
    if draws is None:
        return draw(family, config, seed)
    return reorder(draw(family, config, int(draws)), family.groups(config),
                   np.random.default_rng(seed))


def trace_seeds(seed: int, n: int) -> list:
    """``n`` trace seeds drawn from ``seed``."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    return [int(s) for s in rng.integers(0, 2**62, size=n)]


def scenario_traces(family, config: dict, n: int, seed: int) -> list:
    """``n`` traces of ``config``'s stream, the same set for every run,
    in an order drawn from the run's ``seed``.  The set comes from
    seeds derived from the ``trace`` group's ``draws_seed`` (0 where it
    names none), so a run's seed changes the order of the work and not
    the work: a grid's scenario rows, or the order of a twin's
    episodes."""
    base = int(config["trace"].get("draws_seed", 0))
    traces = [make_trace(family, config, s) for s in trace_seeds(base, n)]
    order = np.random.default_rng(np.random.SeedSequence(seed)).permutation(n)
    return [traces[i] for i in order]
