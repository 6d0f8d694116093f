"""The comparison that decides ``correct``.

Every number compared is printed beside its limit; a run is correct
when no number exceeds its limit and no answer is missing.  The limits
live in each traffic mix's file (``limits``), set from the readings
recorded in PERF.md: the largest that sound runs of the program gave
over a dozen seeds or more, and the smallest that the control (the
reference computed in bfloat16 in the program's place) gave.

Every decision is judged in its own context: the reference rebuilds
the world from the trace and the program's own earlier decisions (they
are facts of that world) and decides there.  So one decision that
differs counts once, and does not drag every later one with it.

Twin cells: the reference rebuilds the twin's mirror from the events
the cluster would publish, and decides.  A cycle is off when the
program's qrun set is not the reference's first-pass starts under the
program's chosen policy, or when that policy's reference score is
worse than the reference's best.

Grid cells: each sampled fork is replayed event by event along the
program's own schedule.  At each pass the set of jobs the program
started at that instant must be the reference's starts, and each
started job must end at its true runtime.  The metrics are compared
with the reference's metrics of the program's schedule, each fork's
cost with the reference's score of those metrics, and the cost of the
fork the program selected with the least of those scores.

Near ties: a priority key is float32 arithmetic with divisions, powers
and exponentials, which a chip may round some units in the last place
apart from numpy.  Where two queued jobs' keys lie that close, either
order is the stated semantics.  So a pass whose starts differ is
judged again under the orders that keys within ``KEY_RTOL`` of each
other could take (``reference.near_tie_orders``); a pass that then
agrees counts as a tie and not as off.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List, NamedTuple, Sequence

import numpy as np

from bench import reference as ref

#: Two scores closer than this (relative) are a tie: which of two
#: policies an f32 score picks then rests on summation order.
TIE_RTOL = 1e-5

#: Two priority keys closer than this (relative, on each side) may rank
#: either way: far above the float32 rounding of a key computed by
#: exp/log (a few 1e-6), far below bfloat16's (about 4e-3).
KEY_RTOL = 2e-5


class Cycle(NamedTuple):
    """One recorded decision of the program: its time, the policy it
    chose (pool index), its per-policy costs and its qrun set."""
    time: float
    best: int
    costs: Sequence[float]
    started: Sequence[int]


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-12)


def _tied(cost: float, best: float) -> bool:
    return cost <= best or (math.isfinite(cost)
                            and cost - best <= TIE_RTOL * abs(best))


def twin_episode(trace, total_nodes: int, pool: Sequence[ref.Policy],
                 cycles: Sequence[Cycle], dt=ref.F32,
                 alternative=None) -> Dict[str, float]:
    """Judge one co-simulated episode's decisions.

    ``alternative``, when given, is a second decider judged at the same
    mirrors in the program's place (the control): a function of the
    mirror returning a ``Cycle``.  Returns counts: cycles judged, cycles
    off, cycles missing or extra, and the widest relative gap between
    the program's and the reference's per-policy costs."""
    f32 = np.float32
    n = len(trace)
    est32 = trace.est_runtime.astype(f32)
    true32 = trace.true_runtime.astype(f32).astype(np.float64)
    nodes = trace.nodes.astype(np.int64)
    heap = [(float(f32(trace.submit_t[j])), j, 0, j) for j in range(n)]
    seq = n
    # the mirror, as the twin's sync stage builds it from the events
    m_submit = np.full(n, -1.0, f32)
    m_est = np.zeros(n, f32)
    m_start = np.full(n, -1.0, f32)
    m_end = np.full(n, -1.0, f32)
    m_state = np.zeros(n, np.int64)
    now = f32(0.0)
    # the cluster's ground truth
    state = np.zeros(n, np.int64)
    end_seq = np.full(n, -1, np.int64)
    free = int(total_nodes)
    pending: List = []
    out = {"judged": 0, "off": 0, "missing": 0, "ties": 0, "cost_gap": 0.0}
    i = 0
    while heap:
        t, s, kind, j = heapq.heappop(heap)
        if kind == 1 and (state[j] != ref.RUNNING or s != end_seq[j]):
            continue
        for jj, tr in pending:                         # RUNJOB events
            m_state[jj], m_start[jj] = ref.RUNNING, f32(tr)
            m_end[jj] = m_start[jj] + m_est[jj]
            now = max(now, f32(tr))
        pending = []
        if kind == 0:                                  # QUEUEJOB
            state[j] = m_state[j] = ref.QUEUED
            m_submit[j], m_est[j] = f32(t), est32[j]
        else:                                          # JOBOBIT
            state[j] = m_state[j] = ref.DONE
            free += int(nodes[j])
            m_end[j] = f32(t)
        now = max(now, f32(t))
        mirror = ref.Mirror(m_submit.astype(dt), nodes, m_est.astype(dt),
                            m_start.astype(dt), m_end.astype(dt),
                            m_state.copy(), free, now)
        if i >= len(cycles):
            out["missing"] += 1
            i += 1
            continue
        prog = cycles[i] if alternative is None else alternative(mirror)
        d = ref.decide(mirror, pool, dt)
        out["judged"] += 1
        p = int(prog.best)
        started = sorted(int(x) for x in prog.started)
        if (len(prog.costs) != len(pool)
                or not _tied(d.costs[p], float(d.costs.min()))):
            out["off"] += 1
        elif started != d.starts[p]:
            if _first_pass_tied(mirror, pool[p], started, dt):
                out["ties"] += 1
            else:
                out["off"] += 1
        for a, b in zip(prog.costs, d.costs):
            out["cost_gap"] = max(out["cost_gap"], _rel(float(a), float(b)))
        # the cluster applies the program's decision (the emulator's qrun)
        t_cyc = float(now)
        for jj in cycles[i].started:
            if state[jj] != ref.QUEUED:
                continue
            if nodes[jj] > free:
                out["off"] += 1
                continue
            state[jj] = ref.RUNNING
            free -= int(nodes[jj])
            end_seq[jj] = seq
            heapq.heappush(heap, (float(f32(t_cyc + true32[jj])), seq, 1, jj))
            seq += 1
            pending.append((jj, t_cyc))
        i += 1
    out["missing"] += max(0, len(cycles) - i)
    return out


def _first_pass_tied(m: ref.Mirror, pol: ref.Policy, started, dt) -> bool:
    """Whether ``started`` is the first pass over the mirror under some
    order that near ties among the keys allow."""
    running = m.state == ref.RUNNING
    return any(sorted(ref.easy_pass(m.now, m.free, order, m.nodes, m.est,
                                    m.end[running], m.nodes[running],
                                    dt)) == started
               for order in ref.near_tie_orders(
                   pol, m.now, m.submit, m.est, m.nodes,
                   m.state == ref.QUEUED, started, KEY_RTOL, dt))


def reference_cycle(pool: Sequence[ref.Policy], dt):
    """A decider that is the reference itself at ``dt``: the control."""
    def decide(mirror: ref.Mirror) -> Cycle:
        d = ref.decide(mirror, pool, dt)
        return Cycle(float(mirror.now), d.best, d.costs.tolist(),
                     d.starts[d.best])
    return decide


def grid_fork(trace, total_nodes: int, pol: ref.Policy, start: np.ndarray,
              end: np.ndarray, deadlocked: bool) -> Dict[str, int]:
    """Judge one replayed fork pass by pass along its own schedule:
    ``start``/``end`` (n,) are the program's actual times.  Returns the
    passes judged, the decisions off (a pass's starts, a job's end, a
    start no pass made, the deadlock flag) and the near ties."""
    f32 = np.float32
    n = len(trace)
    submit = trace.submit_t.astype(f32)
    est = trace.est_runtime.astype(f32)
    true = trace.true_runtime.astype(f32)
    nodes = trace.nodes.astype(np.int64)
    start, end = np.asarray(start, f32), np.asarray(end, f32)
    state = np.zeros(n, np.int64)
    pred_end = np.full(n, f32(-1.0))
    ends: list = []                     # (program's end, pass, slot)
    free, cursor, passes = int(total_nodes), 0, 0
    out = {"passes": 0, "off": 0, "ties": 0}
    inf = f32(np.inf)
    while True:
        t_arr = submit[cursor] if cursor < n else inf
        t_end = ends[0][0] if ends else inf
        if not (np.isfinite(t_arr) or np.isfinite(t_end)):
            break
        if t_arr <= t_end:
            state[cursor] = ref.QUEUED
            cursor += 1
            now = t_arr
        else:
            _, _, j = heapq.heappop(ends)
            state[j] = ref.DONE
            free += int(nodes[j])
            now = t_end
        queued = state == ref.QUEUED
        if not queued.any():
            continue
        running = state == ref.RUNNING
        made = sorted(np.flatnonzero(queued & (start == now)).tolist())
        ranked = ref.priority_order(pol, now, submit, est, nodes, queued)
        want = sorted(ref.easy_pass(now, free, ranked, nodes, est,
                                    pred_end[running], nodes[running]))
        out["passes"] += 1
        if want != made:
            t_next = min(submit[cursor] if cursor < n else inf,
                         ends[0][0] if ends else inf)
            if t_next == now and set(want) < set(made):
                made = want      # the rest start at a later pass of now
            else:
                ok = any(sorted(ref.easy_pass(now, free, order, nodes, est,
                                              pred_end[running],
                                              nodes[running])) == made
                         for order in ref.near_tie_orders(
                             pol, now, submit, est, nodes, queued, made,
                             KEY_RTOL))
                out["ties" if ok else "off"] += 1
        for j in made:
            if nodes[j] > free or end[j] != f32(now + true[j]):
                out["off"] += 1
            state[j], pred_end[j] = ref.RUNNING, now + est[j]
            free -= int(nodes[j])
            heapq.heappush(ends, (end[j], passes, j))
        passes += 1
    out["off"] += int(((state == ref.QUEUED) & (start >= 0)).sum())
    out["off"] += int(bool((state == ref.QUEUED).any()) != bool(deadlocked))
    return out


def grid_scenario(trace, total_nodes: int, pool: Sequence[ref.Policy],
                  start: np.ndarray, end: np.ndarray, metrics: np.ndarray,
                  costs: np.ndarray, best: int,
                  deadlocked: np.ndarray) -> Dict[str, float]:
    """Judge one scenario's row of a replay grid: ``start``/``end``
    (P, n) are the program's actual times, ``metrics`` (P, 6) its
    metrics, ``costs`` (P,) its scores, ``best`` its selection,
    ``deadlocked`` (P,) its flags."""
    f32 = np.float32
    submit = trace.submit_t.astype(f32)
    true = trace.true_runtime.astype(f32)
    out = {"scenarios": 1, "forks": len(pool), "forks_off": 0,
           "passes": 0, "ties": 0, "metric_gap": 0.0, "cost_gap": 0.0}
    own = []
    for p, pol in enumerate(pool):
        fork = grid_fork(trace, total_nodes, pol, start[p], end[p],
                         deadlocked[p])
        out["forks_off"] += int(fork["off"] > 0)
        out["passes"] += fork["passes"]
        out["ties"] += fork["ties"]
        m = ref.replay_metrics(np.asarray(start[p], f32),
                               np.asarray(end[p], f32), submit,
                               trace.nodes, true, total_nodes)
        own.append(m)
        for a, b in zip(metrics[p], m):
            out["metric_gap"] = max(out["metric_gap"], _rel(float(a),
                                                            float(b)))
    scores, _ = ref.select(np.stack(own))
    gaps = [_rel(float(a), float(b)) for a, b in zip(costs, scores)]
    least = float(scores.min())
    gaps.append((float(scores[best]) - least) / max(abs(least), 1e-12))
    out["cost_gap"] = max(gaps)
    return out


def reference_replays(trace, total_nodes: int, pool, dt):
    """The control's answers for one scenario: the reference at ``dt``
    in the program's place, in the program's output layout."""
    reps = [ref.replay(trace, total_nodes, pol, dt) for pol in pool]
    start = np.stack([r.start.astype(np.float32) for r in reps])
    end = np.stack([r.end.astype(np.float32) for r in reps])
    metrics = np.stack([r.metrics for r in reps])
    dead = np.array([r.deadlocked for r in reps])
    costs, best = ref.select(metrics)
    return start, end, metrics, costs, best, dead


def twin_numbers(totals: Dict[str, float]) -> Dict[str, float]:
    """The numbers compared, from summed per-episode counts: the share
    of cycles off or missing in %, the widest relative cost gap."""
    judged = max(totals["judged"], 1)
    return {"cycles_off_pct": 100.0 * (totals["off"] + totals["missing"])
            / judged,
            "cost_gap": totals["cost_gap"]}


def grid_numbers(totals: Dict[str, float]) -> Dict[str, float]:
    """The numbers compared, from summed per-scenario counts: the share
    of forks off in %, the widest relative metric and cost gaps."""
    forks = max(totals["forks"], 1)
    return {"forks_off_pct": 100.0 * totals["forks_off"] / forks,
            "metric_gap": totals["metric_gap"],
            "cost_gap": totals["cost_gap"]}


def merge(parts: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Sum counts, take the widest gap."""
    out: Dict[str, float] = {}
    for part in parts:
        for k, v in part.items():
            if k.endswith("gap"):
                out[k] = max(out.get(k, 0.0), v)
            else:
                out[k] = out.get(k, 0) + v
    return out
