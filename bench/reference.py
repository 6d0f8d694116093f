"""Plain numpy reference scheduler: the yardstick that decides `correct`.

A straightforward, sequential implementation of the semantics the
program states (DESIGN.md §2-§8), written from the description and
importing nothing of the program:

* priority keys: a policy is a family (``lin``, ``wfp``, ``expf``) with
  linear weights over the job features (wait, est, nodes, area,
  xfactor, submit) and the WFP exponents / aging timescale; keys are
  ranked by a stable sort, ties in slot (submission) order;
* one scheduling pass: greedy starts in priority order until the first
  job that does not fit (the head), then EASY backfill against the
  head's reservation.  At the shadow instant every running job that
  ends at or before it has freed its nodes;
* the twin's decision: every policy drains a copy of the mirror to an
  empty queue over predicted ends; the paper's 4-term score over the
  jobs queued at decision time picks the policy (first in pool order on
  a tie), and that policy's first-pass starts are the qrun set;
* the replay: one event at a time, the next arrival or the next true
  completion (arrivals first on equal times, completions in start
  order), one pass after each event;
* the metrics and the score.

All event arithmetic runs in the dtype it is given: float32, as the
configurations state, for the reference; bfloat16 for the control.
"""
from __future__ import annotations

import heapq
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

QUEUED, RUNNING, DONE = 1, 2, 3
EST_FLOOR = 1.0
AGING_CAP = 30.0
SLOWDOWN_TAU = 10.0
FEATURES = ("wait", "est", "nodes", "area", "xfactor", "submit")
F32 = np.dtype(np.float32)
BF16 = np.dtype(ml_dtypes.bfloat16)


class Policy(NamedTuple):
    family: str                     # "lin" | "wfp" | "expf"
    weights: Tuple[float, ...]      # over FEATURES
    a: float = 3.0
    b: float = 1.0
    tau: float = math.inf


def _lin(**w: float) -> Policy:
    return Policy("lin", tuple(float(w.get(f, 0.0)) for f in FEATURES))


STATICS = {
    "wfp": Policy("wfp", (0.0,) * 6),
    "fcfs": _lin(submit=1.0),
    "sjf": _lin(est=1.0),
    "saf": _lin(area=1.0),
    "ljf": _lin(est=-1.0),
    "lxf": _lin(xfactor=-1.0),
    "expf": Policy("expf", (0.0,) * 6, tau=3600.0),
}
GROUPS = {"paper": ("wfp", "fcfs", "sjf"),
          "extended": ("wfp", "fcfs", "sjf", "saf", "ljf", "lxf", "expf")}


def _values(text: str) -> List[float]:
    if ".." not in text:
        return [float(text)]
    lo, rest = text.split("..")
    hi, n = rest.rsplit("x", 1)
    return [float(v) for v in np.linspace(float(lo), float(hi), int(n))]


def parse_pool(text: str) -> List[Policy]:
    """Pool grammar: comma-separated terms, each a static name, a
    group (``paper``, ``extended``), or ``wfp``/``expf`` with
    ``:param=value`` or ``:param=lo..hixN`` sweeps whose cartesian
    product runs rightmost fastest.  Term order is tie-break order."""
    pool: List[Policy] = []
    for term in (t.strip().lower() for t in text.split(",")):
        head, *assigns = term.split(":")
        if not assigns:
            pool.extend(STATICS[n] for n in GROUPS.get(head, (head,)))
            continue
        names = [a.split("=")[0] for a in assigns]
        grids = [_values(a.split("=")[1]) for a in assigns]
        base = STATICS[head]
        for combo in np.array(np.meshgrid(*grids, indexing="ij")).reshape(
                len(grids), -1).T:
            pool.append(base._replace(**dict(zip(names, combo.tolist()))))
    return pool


def _pow(x: np.ndarray, p: float) -> np.ndarray:
    if p == 1.0:
        return x
    if p == 2.0:
        return x * x
    if p == 3.0:
        return x * x * x
    return np.power(x, x.dtype.type(p))


def priority_keys(pol: Policy, now, submit, est, nodes, idx,
                  dt=F32) -> np.ndarray:
    """The policy's keys (lowest runs first) of the slots ``idx``."""
    c = dt.type
    sub, e = submit[idx], np.maximum(est[idx], c(EST_FLOOR))
    n = nodes[idx].astype(dt)
    wait = np.maximum(c(now) - sub, c(0.0))
    feats = (wait, e, n, n * e, (wait + e) / e, sub)
    key = np.zeros(idx.shape, dt)
    for w, f in zip(pol.weights, feats):
        if w != 0.0:
            key = key + c(w) * f
    if pol.family != "lin":
        aged = np.minimum(wait / c(pol.tau), c(AGING_CAP))
        if pol.family == "wfp":
            util = _pow(wait / e, pol.a) * _pow(n, pol.b) * np.exp(aged)
        else:
            util = np.expm1(aged)
        key = key - util
    return key


def priority_order(pol: Policy, now, submit, est, nodes, queued,
                   dt=F32) -> np.ndarray:
    """Queued slots ranked by the policy's key (lowest first), ties in
    slot order."""
    idx = np.flatnonzero(queued)
    key = priority_keys(pol, now, submit, est, nodes, idx, dt)
    return idx[np.argsort(key, kind="stable")]


def near_tie_orders(pol: Policy, now, submit, est, nodes, queued,
                    prefer, rtol: float, dt=F32):
    """Orders of the queued slots that keys a few roundings apart could
    give, every two keys within ``rtol`` of each other (relative) taken
    as tied: first with each tie broken for the slots in ``prefer``,
    then the plain order with one adjacent tied pair swapped, for each
    such pair."""
    idx = np.flatnonzero(queued)
    key = priority_keys(pol, now, submit, est, nodes, idx, dt).astype(
        np.float64)
    shift = rtol * np.abs(key)
    yield idx[np.argsort(np.where(np.isin(idx, list(prefer)),
                                  key - shift, key + shift), kind="stable")]
    plain = np.argsort(key, kind="stable")
    k = key[plain]
    for i in np.flatnonzero(np.abs(np.diff(k)) <= 2 * rtol * np.maximum(
            np.abs(k[1:]), np.abs(k[:-1]))):
        order = plain.copy()
        order[i], order[i + 1] = order[i + 1], order[i]
        yield idx[order]


def easy_pass(now, free, ranked, nodes, est, run_end, run_nodes,
              dt=F32) -> List[int]:
    """One pass over the queued slots ``ranked`` (priority order):
    greedy starts until the head blocks, then EASY backfill.  Running
    jobs are given by their predicted ends ``run_end`` and node counts.
    Returns the started slots."""
    c = dt.type
    now = c(now)
    started: List[int] = []
    head = -1
    for r, j in enumerate(ranked):
        if nodes[j] > free:
            head = r
            break
        started.append(int(j))
        free -= int(nodes[j])
    if head < 0:
        return started
    head_nodes = int(nodes[ranked[head]])
    # reservation: the earliest predicted end at which the nodes freed
    # by every job ending at or before it cover the head
    ends = np.concatenate([run_end.astype(dt),
                           now + est[started].astype(dt)])
    held = np.concatenate([run_nodes, nodes[started]]).astype(np.int64)
    order = np.argsort(ends, kind="stable")
    ends, cum = ends[order], free + np.cumsum(held[order])
    last_of_tie = np.append(ends[1:] != ends[:-1], True)
    ok = last_of_tie & (cum >= head_nodes)
    if ok.any():
        first = int(np.argmax(ok))
        shadow, extra = ends[first], int(cum[first]) - head_nodes
    else:
        shadow, extra = c(np.inf), 0
    rest = ranked[head + 1:]
    rest = rest[nodes[rest] <= free]     # free only falls from here on
    before = ((now + est[rest].astype(dt)) <= shadow).tolist()
    for j, a in zip(rest.tolist(), before):
        n = int(nodes[j])
        if n <= free and (a or n <= extra):
            started.append(j)
            free -= n
            if not a:
                extra -= n
            if free == 0:
                break
    return started


class Mirror(NamedTuple):
    """The twin's view of the cluster at a decision: times in ``dt``."""
    submit: np.ndarray
    nodes: np.ndarray
    est: np.ndarray
    start: np.ndarray
    end: np.ndarray     # predicted end for running jobs
    state: np.ndarray
    free: int
    now: object


def drain(m: Mirror, pol: Policy, dt=F32):
    """Drain one copy of the mirror under ``pol`` over predicted ends.
    Returns (first-pass starts, start times, deadlocked)."""
    c = dt.type
    state, start, end = m.state.copy(), m.start.copy(), m.end.copy()
    free, now = int(m.free), c(m.now)
    first: Optional[List[int]] = None
    for _ in range(state.shape[0] + 1):
        queued = state == QUEUED
        if not queued.any():
            break
        running = state == RUNNING
        ranked = priority_order(pol, now, m.submit, m.est, m.nodes,
                                queued, dt)
        new = easy_pass(now, free, ranked, m.nodes, m.est, end[running],
                        m.nodes[running], dt)
        if first is None:
            first = new
        for j in new:
            state[j], start[j], end[j] = RUNNING, now, now + m.est[j]
            free -= int(m.nodes[j])
        running = state == RUNNING
        if not (state == QUEUED).any():
            break
        if not running.any():
            return first, start, True
        t_next = max(end[running].min(), now)
        ending = running & (end <= t_next)
        state[ending] = DONE
        free += int(m.nodes[ending].sum())
        now = t_next
    return first or [], start, False


def score(wait: np.ndarray, slowdown: np.ndarray) -> float:
    """The paper's score: 0.25·(max wait + avg wait in minutes) +
    0.25·(max + avg bounded slowdown), a cost to minimize."""
    w = wait.astype(np.float64)
    s = slowdown.astype(np.float64)
    return 0.25 * (w.max() / 60.0 + s.max() + w.mean() / 60.0 + s.mean())


def slowdown(wait: np.ndarray, runtime: np.ndarray) -> np.ndarray:
    c = wait.dtype.type
    return np.maximum((wait + runtime) / np.maximum(runtime, c(SLOWDOWN_TAU)),
                      c(1.0))


class Decision(NamedTuple):
    costs: np.ndarray            # (k,) f64, inf where the drain deadlocks
    best: int
    starts: List[List[int]]      # first-pass starts per policy


def decide(m: Mirror, pool: Sequence[Policy], dt=F32) -> Decision:
    """The twin's decision over the mirror ``m``."""
    c = dt.type
    evald = np.flatnonzero(m.state == QUEUED)
    costs, starts = [], []
    for pol in pool:
        first, start, dead = drain(m, pol, dt)
        starts.append(sorted(first))
        if dead:
            costs.append(math.inf)
            continue
        if evald.size == 0:          # nothing queued: every wait is 0
            costs.append(0.25)
            continue
        wait = np.maximum(start[evald] - m.submit[evald], c(0.0))
        costs.append(score(wait, slowdown(wait, m.est[evald])))
    costs = np.asarray(costs, np.float64)
    return Decision(costs, int(np.argmin(costs)), starts)


# ----------------------------------------------------------------------
# Replay: one trace under one policy, event by event.
# ----------------------------------------------------------------------

class Replay(NamedTuple):
    start: np.ndarray     # actual start times, (n,)
    end: np.ndarray       # actual end times, (n,)
    metrics: np.ndarray   # avg_wait, max_wait, avg_sd, max_sd, makespan, util
    deadlocked: bool


def replay(trace, total_nodes: int, pol: Policy, dt=F32) -> Replay:
    """Replay ``trace`` (``gen.Trace``) on ``total_nodes`` under ``pol``:
    the scheduler sees estimates, completions come at true runtimes."""
    c = dt.type
    n = len(trace)
    submit = trace.submit_t.astype(F32).astype(dt)
    est = trace.est_runtime.astype(F32).astype(dt)
    true = trace.true_runtime.astype(F32).astype(dt)
    nodes = trace.nodes.astype(np.int64)
    state = np.zeros(n, np.int64)
    start = np.full(n, c(-1.0), dt)
    end = np.full(n, c(-1.0), dt)        # predicted while running
    ends: list = []                      # heap of (true end, pass, slot)
    free, now, cursor, passes, dead = int(total_nodes), c(0.0), 0, 0, False
    while True:
        t_arr = submit[cursor] if cursor < n else c(np.inf)
        t_end = ends[0][0] if ends else c(np.inf)
        if not (np.isfinite(t_arr) or np.isfinite(t_end)):
            dead = bool((state == QUEUED).any())
            break
        if t_arr <= t_end:
            state[cursor] = QUEUED
            cursor += 1
            now = t_arr
        else:
            t, _, j = heapq.heappop(ends)
            state[j], end[j] = DONE, t
            free += int(nodes[j])
            now = t
        queued = state == QUEUED
        if not queued.any():
            continue
        running = state == RUNNING
        ranked = priority_order(pol, now, submit, est, nodes, queued, dt)
        new = easy_pass(now, free, ranked, nodes, est, end[running],
                        nodes[running], dt)
        for j in sorted(new):
            state[j], start[j], end[j] = RUNNING, now, now + est[j]
            free -= int(nodes[j])
            heapq.heappush(ends, (now + true[j], passes, j))
        passes += 1
    return Replay(start, end, replay_metrics(start, end, submit, nodes, true,
                                             total_nodes), dead)


def replay_metrics(start, end, submit, nodes, runtime,
                   total_nodes: int) -> np.ndarray:
    """avg/max wait, avg/max bounded slowdown, makespan, utilization of
    one replayed trace, with runtimes the true ones."""
    c = start.dtype.type
    wait = np.maximum(start - submit, c(0.0))
    sd = slowdown(wait, runtime)
    makespan = end.max()
    w64 = lambda x: x.astype(np.float64)                   # noqa: E731
    span = max(float(makespan) - float(submit.min()), 1e-6)
    util = float((w64(nodes) * w64(runtime)).sum()) / (total_nodes * span)
    return np.array([w64(wait).mean(), float(wait.max()), w64(sd).mean(),
                     float(sd.max()), float(makespan), min(max(util, 0.0),
                                                           1.0)])


def select(metrics: np.ndarray) -> Tuple[np.ndarray, int]:
    """Per-policy scores of replay metrics (P, 6) and the winner."""
    costs = 0.25 * (metrics[:, 1] / 60.0 + metrics[:, 3]
                    + metrics[:, 0] / 60.0 + metrics[:, 2])
    return costs, int(np.argmin(costs))
