"""Closed-loop co-simulation as ``launch/twin_loop`` runs it:
``ClusterEmulator`` -> ``EventBus`` -> ``SchedTwin.pump`` -> ``qrun``,
one episode per step, with a fresh emulator, bus and twin, the steps
cycling through the mix's ``episodes`` traces in the seed's order.
Every pump that records a decision cycle is timed on the host clock
from the emulator's call to its return."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, gen
from bench import reference as ref
from bench.readings import quantile
from bench.record import Record, span


class Driver:
    pass_k = pass_j = 0
    spans = ("emulator", "pump")

    def __init__(self, cell, seed: int):
        from repro.core.engine import DrainEngine
        from repro.core.policies import parse_pool
        self.config, self.traffic = cell.config, cell.traffic
        self.nodes = int(self.config["total_nodes"])
        self.max_jobs = int(self.config["max_jobs"])
        self.pool = parse_pool(self.traffic["pool"])
        self.names = list(self.pool.names)
        self.goal = self.traffic["goal"]
        self.engine = DrainEngine(self.traffic["backend"])
        self.traces = gen.scenario_traces(cell.family, self.config,
                                          int(self.traffic["episodes"]), seed)
        self._steps = 0
        self.episodes: List[tuple] = []      # (trace, [check.Cycle])
        self.failed = 0

    def _next_trace(self) -> gen.Trace:
        self._steps += 1
        return self.traces[(self._steps - 1) % len(self.traces)]

    def _episode(self, trace, record: Optional[Record], traced: bool,
                 check_invariants: bool = False):
        from repro.cluster.emulator import ClusterEmulator
        from repro.core.events import EventBus
        from repro.core.twin import SchedTwin
        bus = EventBus()
        em = ClusterEmulator(gen.jobspecs(trace), self.nodes, bus=bus,
                             max_jobs=self.max_jobs,
                             check_invariants=check_invariants,
                             engine=self.engine)
        twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=self.nodes,
                         max_jobs=self.max_jobs, pool=self.pool,
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
                record.add("pump_s", dt)
                record.add("decide_s", cycles[-1].wall_seconds)
                record.add("traced", float(traced))

        with span("emulator", traced):
            em.run(on_event=pump, on_quiesce=twin.flush)
        return twin, bus

    def warm(self) -> None:
        """One whole episode, invariants checked: every shape and every
        job slot the window's episodes use is compiled here."""
        twin, bus = self._episode(self._next_trace(), None, False,
                                  check_invariants=True)
        if twin.dead_letters or bus.health()["callback_failures"]:
            raise RuntimeError("the warm-up episode failed")

    def step(self, record: Record, traced: bool) -> None:
        trace = self._next_trace()
        twin, bus = self._episode(trace, record, traced)
        cycles = twin.telemetry.cycles
        record.attempted += len(cycles)
        self.failed += (len(twin.dead_letters)
                        + int(bus.health()["callback_failures"]))
        self.episodes.append((trace, [
            check.Cycle(c.time, self.names.index(c.policy),
                        [c.costs[n] for n in self.names], c.started_jobs)
            for c in cycles]))

    def memory(self) -> None:
        """Nothing outlives an episode on the device."""

    def failures(self) -> int:
        """Dead letters and bus callback failures over the window."""
        return self.failed

    def judge(self, seed: int, control=None) -> Dict[str, float]:
        """The reference's verdict on a seeded sample of the window's
        episodes, the longest among them."""
        n = int(self.traffic["check_episodes"])
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        longest = max(range(len(self.episodes)),
                      key=lambda i: len(self.episodes[i][1]))
        rest = [i for i in range(len(self.episodes)) if i != longest]
        pick = [longest] + list(rng.permutation(rest)[:n - 1])
        pool = ref.parse_pool(self.traffic["pool"])
        alt = check.reference_cycle(pool, control) if control else None
        parts = [check.twin_episode(self.episodes[i][0], self.nodes, pool,
                                    self.episodes[i][1], alternative=alt)
                 for i in pick]
        self.totals = check.merge(parts)
        return check.twin_numbers(self.totals)

    def report(self, record: Record) -> list:
        """The window's cycle count, and the traced stretch's own pump
        median beside the window's: the profiler slows the host there,
        so the traced idle share includes that slowing."""
        pump = record.samples.get("pump_s", [])
        flags = record.samples.get("traced", [])
        window = [p for p, t in zip(pump, flags) if not t]
        traced = [p for p, t in zip(pump, flags) if t]
        lines = [f"cycles timed: {len(window)}"]
        if traced and window:
            lines.append(f"traced stretch: {len(traced)} cycles, pump p50 "
                         f"{1e3 * quantile(traced, 0.5):.3f} ms against "
                         f"{1e3 * quantile(window, 0.5):.3f} ms in the "
                         f"window")
        return lines
