"""The comparison that decides ``correct`` for a twin attached to a
running scheduler mid-stream.

``check.twin_episode`` judges a twin that saw the stream from time 0.
Here the world starts from a snapshot of the cluster
(``ClusterEmulator.snapshot_state``) taken where the scheduler had
already run part of the stream on its own: the jobs it holds, the
clock, the free nodes and the event heap (arrivals still to come and
the true ends of running jobs).  The twin's mirror at attach is the
scheduler's job view at that instant: queued jobs, and running jobs
with predicted ends (start + estimate).  Its first decision is made
there if anything is queued; every later one follows one event of the
stream, a submission or a completion, as in ``twin_episode``.

Every decision is judged in its own context, the world rebuilt from
the snapshot, the trace and the program's own earlier decisions, with
the reference's ``decide``, near-tie rule and limits
(``bench/check.py``).  Only the cycles in ``judge`` are decided by the
reference; every cycle's qrun set is applied to the world, and one the
cluster could not start counts as off.
"""
from __future__ import annotations

import heapq
from typing import Collection, Dict, Sequence

import numpy as np

from bench import check
from bench import reference as ref


def attach_episode(trace, total_nodes: int, pool: Sequence[ref.Policy],
                   snapshot: dict, cycles: Sequence[check.Cycle],
                   judge: Collection[int], dt=ref.F32,
                   alternative=None) -> Dict[str, float]:
    """Judge the cycles ``judge`` (indices into ``cycles``) of a twin
    attached at ``snapshot``.  ``alternative``, when given, decides in
    the program's place at the same mirrors (the control).  Returns the
    counts of ``check.twin_episode``."""
    f32 = np.float32
    n = len(trace)
    est32 = trace.est_runtime.astype(f32)
    true32 = trace.true_runtime.astype(f32).astype(np.float64)
    nodes = trace.nodes.astype(np.int64)
    # the cluster's ground truth at the snapshot
    state = np.asarray(snapshot["state"], np.int64)[:n].copy()
    end_seq = np.asarray(snapshot["end_seq"], np.int64)[:n].copy()
    free = int(snapshot["free_nodes"])
    heap = [(float(t), int(s), int(k), int(j))
            for t, s, k, j in snapshot["heap"]]
    heapq.heapify(heap)
    seq = int(snapshot["seq"])
    # the twin's mirror, as it attaches from the scheduler's job view
    running = state == ref.RUNNING
    m_state = state.copy()
    m_submit = np.asarray(snapshot["submit_t"], np.float64)[:n].astype(f32)
    m_est = np.where(state != 0, est32, f32(0.0))
    m_start = np.where(running, np.asarray(snapshot["start_t"],
                                           np.float64)[:n], -1.0).astype(f32)
    m_end = np.where(running, m_start + m_est, f32(-1.0)).astype(f32)
    now = f32(snapshot["now"])
    pending: list = []
    out = {"judged": 0, "off": 0, "missing": 0, "ties": 0, "cost_gap": 0.0}
    i = 0
    first = bool((m_state == ref.QUEUED).any())    # the attach decision
    while i < len(cycles):
        if not first:
            if not heap:
                out["missing"] += len(cycles) - i
                break
            t, s, kind, j = heapq.heappop(heap)
            if kind == 1 and (state[j] != ref.RUNNING or s != end_seq[j]):
                continue
            for jj, tr in pending:                     # RUNJOB events
                m_state[jj], m_start[jj] = ref.RUNNING, f32(tr)
                m_end[jj] = m_start[jj] + m_est[jj]
                now = max(now, f32(tr))
            pending = []
            if kind == 0:                              # QUEUEJOB
                state[j] = m_state[j] = ref.QUEUED
                m_submit[j], m_est[j] = f32(t), est32[j]
            else:                                      # JOBOBIT
                state[j] = m_state[j] = ref.DONE
                free += int(nodes[j])
                m_end[j] = f32(t)
            now = max(now, f32(t))
        first = False
        if i in judge:
            mirror = ref.Mirror(m_submit.astype(dt), nodes, m_est.astype(dt),
                                m_start.astype(dt), m_end.astype(dt),
                                m_state.copy(), free, now)
            prog = cycles[i] if alternative is None else alternative(mirror)
            d = ref.decide(mirror, pool, dt)
            out["judged"] += 1
            p = int(prog.best)
            started = sorted(int(x) for x in prog.started)
            if (len(prog.costs) != len(pool)
                    or not check._tied(d.costs[p], float(d.costs.min()))):
                out["off"] += 1
            elif started != d.starts[p]:
                if check._first_pass_tied(mirror, pool[p], started, dt):
                    out["ties"] += 1
                else:
                    out["off"] += 1
            for a, b in zip(prog.costs, d.costs):
                out["cost_gap"] = max(out["cost_gap"],
                                      check._rel(float(a), float(b)))
        # the cluster applies the program's decision (the emulator's qrun)
        t_cyc = float(now)
        for jj in cycles[i].started:
            if not 0 <= jj < n or state[jj] != ref.QUEUED:
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
    return out
