"""``DrainEngine.replay_grid`` over S scenarios built in set-up × the
pool, the scenario rows in the seed's order; one step is one call,
ended by ``block_until_ready``."""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench import check, gen
from bench import reference as ref
from bench.record import Record, span


class Driver:
    spans = ("grid_call",)

    def __init__(self, cell, seed: int):
        from repro.cluster.workload import stack_scenarios
        from repro.core.engine import DrainEngine
        from repro.core.policies import parse_pool
        self.config, self.traffic = cell.config, cell.traffic
        self.nodes = int(self.config["total_nodes"])
        S = int(self.traffic["scenarios"])
        self.traces = gen.scenario_traces(cell.family, self.config, S, seed)
        self.scenarios = stack_scenarios(
            [gen.jobspecs(t) for t in self.traces], self.nodes,
            max_jobs=int(self.config["max_jobs"]))
        self.pool = parse_pool(self.traffic["pool"])
        self.engine = DrainEngine(self.traffic["backend"])
        self.goal = self.traffic["goal"]
        self.forks = S * len(self.pool)
        self.pass_k = self.forks
        self.pass_j = int(self.config["max_jobs"])
        self.out = None
        self.n_calls = 0
        self._call = lambda: self.engine.replay_grid(
            self.scenarios, self.pool.spec, self.goal)

    def warm(self) -> None:
        import jax
        jax.block_until_ready(self._call())

    def step(self, record: Record, traced: bool) -> None:
        import jax
        with span("grid_call", traced):
            t0 = time.perf_counter()
            out = self._call()
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
        record.calls.append({"forks": self.forks, "seconds": dt,
                             "iters": int(out.result.iters),
                             "passes": int(out.result.pass_invocations),
                             "traced": traced})
        record.attempted += self.forks
        self.n_calls += 1
        self.out = out

    def memory(self) -> None:
        """Keep the last call's answers on the host, free the device."""
        out = self.out
        self.answers = {
            "start": np.asarray(out.start_t), "end": np.asarray(out.end_t),
            "metrics": np.stack([np.asarray(f) for f in out.metrics],
                                axis=-1),
            "costs": np.asarray(out.costs),
            "best": np.asarray(out.best),
            "deadlocked": np.asarray(out.deadlocked)}
        self.out = None

    def failures(self) -> int:
        """Deadlocked forks and non-finite metrics, over every call."""
        a = self.answers
        bad = a["deadlocked"] | ~np.isfinite(a["metrics"]).all(axis=-1)
        return int(bad.sum()) * self.n_calls

    def judge(self, seed: int, control=None) -> Dict[str, float]:
        """The reference's verdict on every fork of a seeded sample of
        the scenarios."""
        n = int(self.traffic["check_scenarios"])
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        pick = rng.permutation(len(self.traces))[:n]
        pool = ref.parse_pool(self.traffic["pool"])
        a = self.answers
        parts = []
        for s in pick:
            tr = self.traces[s]
            m = len(tr)
            if control:
                answers = check.reference_replays(tr, self.nodes, pool,
                                                  control)
            else:
                answers = (a["start"][s, :, :m], a["end"][s, :, :m],
                           a["metrics"][s], a["costs"][s], int(a["best"][s]),
                           a["deadlocked"][s])
            parts.append(check.grid_scenario(tr, self.nodes, pool,
                                             *answers))
        self.totals = check.merge(parts)
        return check.grid_numbers(self.totals)

    def report(self, record: Record) -> list:
        if not record.calls:
            return []
        secs = sorted(c["seconds"] for c in record.calls)
        return [f"grid calls: {len(secs)}, seconds min {secs[0]:.4f} "
                f"median {secs[len(secs) // 2]:.4f} max {secs[-1]:.4f}"]
