"""A twin attached to a scheduler that already has a deep queue:
``ClusterEmulator`` -> ``EventBus`` -> ``SchedTwin.pump`` -> ``qrun``
from a snapshot taken mid-stream.

In set-up each of the mix's ``traces`` traces of the configuration's
stream is run by the emulator on its own (static mode under
``static_policy``, with EASY backfill), and the cluster is snapshotted
right after the submission in ``attach_range`` after which its queue
is deepest.  One step restores a snapshot into a
fresh emulator and bus, attaches a fresh twin whose job table holds
``window`` slots (below every attach id, so slots are recycled from the
first event), and runs the closed loop for ``cycles`` decision cycles
after the attach; the steps cycle through the snapshots in the seed's
order.  Every pump that records a decision cycle is timed on the host
clock from the emulator's call to its return, as in ``twin.py``; the
attach is timed apart."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from bench import attach, check, gen
from bench import reference as ref
from bench.readings import quantile, untraced
from bench.record import Record, span


class Driver:
    pass_k = pass_j = 0
    spans = ("emulator", "pump", "attach")

    def __init__(self, cell, seed: int):
        from repro.core.engine import DrainEngine
        from repro.core.policies import parse_pool
        from repro.core.twin import SchedTwin
        if not hasattr(SchedTwin, "attach"):
            raise SystemExit("twin_attach: this program's SchedTwin cannot "
                             "attach to a running scheduler")
        self.config, self.traffic = cell.config, cell.traffic
        self.nodes = int(self.config["total_nodes"])
        self.window = int(self.traffic["window"])
        self.cycles = int(self.traffic["cycles"])
        self.pool = parse_pool(self.traffic["pool"])
        self.names = list(self.pool.names)
        self.goal = self.traffic["goal"]
        self.engine = DrainEngine(self.traffic["backend"])
        self.traces = gen.scenario_traces(cell.family, self.config,
                                          int(self.traffic["traces"]), seed)
        self.snapshots = [self._attach_point(t) for t in self.traces]
        self._steps = 0
        # (trace index, [check.Cycle], live jobs per cycle)
        self.episodes: List[tuple] = []
        self.failed = 0

    def _emulator(self, trace, bus=None):
        from repro.cluster.emulator import ClusterEmulator
        return ClusterEmulator(gen.jobspecs(trace), self.nodes, bus=bus,
                               max_jobs=len(trace), engine=self.engine)

    def _attach_point(self, trace) -> dict:
        """The emulator's snapshot after the submission in
        ``attach_range`` at which its own scheduler's queue is deepest
        (the earliest such; one that another event shares is passed
        over).  The static run is one device replay of the trace
        (``run(fast=True)``, the host loop's times bit for bit), cut at
        that submission (``ClusterEmulator.start_at``)."""
        from repro.core.policies import parse_pool
        lo, hi = (int(x) for x in self.traffic["attach_range"])
        policy = parse_pool(self.traffic["static_policy"]).spec
        report = self._emulator(trace).run(policy_id=policy, fast=True)
        sub = trace.submit_t.astype(np.float32)
        depth = [int(np.count_nonzero(report.start_t[:a + 1] > sub[a]))
                 for a in range(lo, hi)]
        for i in np.lexsort((np.arange(len(depth)), -np.asarray(depth))):
            em = self._emulator(trace)
            try:
                em.start_at(report, lo + int(i))
            except ValueError:
                continue
            return em.snapshot_state()
        raise RuntimeError(f"no submission in [{lo}, {hi}) to attach at")

    def _episode(self, k: int, record: Optional[Record], traced: bool):
        from repro.core.events import EventBus
        from repro.core.twin import SchedTwin
        bus = EventBus()
        em = self._emulator(self.traces[k], bus)
        em.restore_state(self.snapshots[k])
        twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=self.nodes,
                         max_jobs=self.window, pool=self.pool,
                         objective=self.goal,
                         free_nodes_probe=lambda: em.free_nodes,
                         jobs_probe=em.jobs_view, engine=self.engine)
        cycles = twin.telemetry.cycles
        clock = time.perf_counter

        def pump():
            n0 = len(cycles)
            with span("pump", traced):
                t0 = clock()
                twin.pump()
                dt = clock() - t0
            if record is not None and len(cycles) > n0:
                c = cycles[-1]
                record.add("pump_s", dt)
                record.add("decide_s", c.wall_seconds)
                record.add("traced", float(traced))
                record.add("slots_s", c.stages["twin.slots"])
                record.add("live_jobs", float(c.live_jobs))

        with span("attach", traced):
            t0 = clock()
            twin.attach()
            if record is not None:
                record.add("attach_s", clock() - t0)
        n0 = len(cycles)
        with span("emulator", traced):
            ran = em.advance(lambda: len(cycles) - n0 >= self.cycles,
                             on_event=pump)
        return twin, bus, ran

    def warm(self) -> None:
        """One episode from each snapshot: every shape the window uses
        is compiled here."""
        for k in range(len(self.traces)):
            twin, bus, ran = self._episode(k, None, False)
            if (not ran or twin.dead_letters
                    or bus.health()["callback_failures"]):
                raise RuntimeError("a warm-up episode failed")

    def step(self, record: Record, traced: bool) -> None:
        k = self._steps % len(self.traces)
        self._steps += 1
        twin, bus, ran = self._episode(k, record, traced)
        cycles = twin.telemetry.cycles
        record.attempted += len(cycles)
        self.failed += (len(twin.dead_letters) + (not ran)
                        + int(bus.health()["callback_failures"]))
        self.episodes.append((k, [
            check.Cycle(c.time, self.names.index(c.policy),
                        [c.costs[n] for n in self.names], c.started_jobs)
            for c in cycles], [c.live_jobs for c in cycles]))

    def memory(self) -> None:
        """Nothing outlives an episode on the device."""

    def failures(self) -> int:
        """Dead letters (a full job table among them), bus callback
        failures, and episodes whose stream ended before their
        cycles."""
        return self.failed

    def judge(self, seed: int, control=None) -> Dict[str, float]:
        """The reference's verdict on a seeded sample of the window's
        cycles, the one with the most live jobs among them."""
        n = int(self.traffic["check_cycles"])
        flat = [(e, i) for e, (_, cyc, _) in enumerate(self.episodes)
                for i in range(len(cyc))]
        most = max(range(len(flat)),
                   key=lambda f: self.episodes[flat[f][0]][2][flat[f][1]])
        rest = [f for f in range(len(flat)) if f != most]
        rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
        picked: Dict[int, set] = {}
        for f in [most] + list(rng.permutation(rest)[:n - 1]):
            e, i = flat[int(f)]
            picked.setdefault(e, set()).add(i)
        pool = ref.parse_pool(self.traffic["pool"])
        alt = check.reference_cycle(pool, control) if control else None
        parts = [attach.attach_episode(
            self.traces[self.episodes[e][0]], self.nodes, pool,
            self.snapshots[self.episodes[e][0]], self.episodes[e][1],
            judge, alternative=alt) for e, judge in sorted(picked.items())]
        self.totals = check.merge(parts)
        return check.twin_numbers(self.totals)

    def report(self, record: Record) -> list:
        """The window's cycle count, live jobs and attach time, and the
        traced stretch's own pump median beside the window's."""
        window = untraced(record, "pump_s")
        pump = record.samples.get("pump_s", [])
        flags = record.samples.get("traced", [])
        traced = [p for p, t in zip(pump, flags) if t]
        live = untraced(record, "live_jobs")
        lines = [f"cycles timed: {len(window)}"]
        if live:
            lines.append(f"live jobs per cycle: mean {np.mean(live):.1f}, "
                         f"max {max(live):.0f} of {self.window} slots")
        attach_s = record.samples.get("attach_s", [])
        if attach_s:
            lines.append(f"attach: {len(attach_s)}, median "
                         f"{1e3 * quantile(attach_s, 0.5):.3f} ms")
        if traced and window:
            lines.append(f"traced stretch: {len(traced)} cycles, pump p50 "
                         f"{1e3 * quantile(traced, 0.5):.3f} ms against "
                         f"{1e3 * quantile(window, 0.5):.3f} ms in the "
                         f"window")
        return lines
