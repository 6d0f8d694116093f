"""Quickstart: SchedTwin in 40 lines.

Builds the paper's §4.1 setup — a PBS-like 32-node cluster emulator, a
four-phase synthetic workload, and the real-time digital twin — runs
the co-simulation, and prints the adaptive-vs-static comparison.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.cluster import ClusterEmulator, paper_synthetic_trace
from repro.cluster.workload import stack_scenarios
from repro.core import EventBus, SchedTwin
from repro.core.engine import DrainEngine
from repro.core.policies import FCFS, SJF, WFP, parse_pool, policy_name
from repro.core.scoring import radar_report
from repro.core.whatif import sharded_replay_grid
from repro.launch.mesh import make_fleet_mesh

trace = paper_synthetic_trace(seed=0)          # 150 jobs, 4 phases

# --- static baselines (the schedulers the paper compares against) ----
# fast=True replays the whole trace in ONE device computation
# (bit-identical to the per-event host loop, DESIGN.md §6)
per_policy = {}
for pid in (FCFS, WFP, SJF):
    emulator = ClusterEmulator(trace, total_nodes=32)
    report = emulator.run(policy_id=pid, fast=True)
    per_policy[policy_name(pid)] = report.metric_dict()

# --- a whole (scenario x policy) grid in one shot --------------------
# S traces x the 7-policy pool: one batched replay, per-(s, p) metrics.
# The objective (DESIGN.md §8) drives the per-scenario selection —
# here: minimize avg wait subject to >= 70% utilization, with
# feasibility fallback.  Try "avg_wait", "lex:avg_wait,makespan", ...
scenarios = stack_scenarios([paper_synthetic_trace(seed=s)
                             for s in range(4)], total_nodes=32)
pool7 = parse_pool("extended")
grid = DrainEngine().replay_grid(scenarios, pool7.spec,
                                 "min:avg_wait@util>=0.7")
print("grid avg_wait (S=4 x P=7):\n", np.asarray(grid.metrics.avg_wait))
print("per-scenario picks:", [pool7.names[int(b)] for b in grid.best])

# --- fleet scale: the same grid, sharded + streamed ------------------
# The fleet engine (DESIGN.md §9) shards the SCENARIO axis over the
# local device mesh and streams it in fixed-size blocks — one compiled
# shape regardless of S, host-side ingestion of block i+1 overlapping
# the device drain of block i (prefetch_depth), and the §7 static-key
# hoisting applied shard-locally.  Bit-identical to replay_grid above;
# S is unconstrained (inert padding fills the last block).  CLI:
#     python -m repro.launch.twin_loop --replay-grid 1024 \
#         --shard 0 --block-size 128 --prefetch 2
fleet = sharded_replay_grid(make_fleet_mesh(), engine=DrainEngine(),
                            objective="min:avg_wait@util>=0.7",
                            block_size=2, prefetch_depth=2)
big = stack_scenarios([paper_synthetic_trace(seed=s)
                       for s in range(6)], total_nodes=32)
out = fleet(big, pool7.spec)
print("fleet picks (S=6, blocks of 2):",
      [pool7.names[int(b)] for b in out.best])

# --- risk-aware: a Monte-Carlo fan of perturbed futures --------------
# One predicted future per cell is fragile — estimates are wrong and
# nodes fail.  A fan (DESIGN.md §10) grows F perturbed futures per
# (scenario, policy) ON DEVICE from the one uploaded base (runtime
# noise, arrival-burst warps, node-failure draws; member 0 stays
# exact) and selects by a DISTRIBUTIONAL goal: tail quantiles
# ("p95:avg_wait"), CVaR ("cvar:0.9:score"), worst case, or regret.
# FanOutcome.cost_ci / fan_width carry device-computed per-policy
# confidence.  CLI: twin_loop --fan 256 --fan-noise 0.3 [--prune]
from repro.core.fan import FanSpec

fan = DrainEngine().fan_grid(
    scenarios, pool7.spec,
    FanSpec(n=64, runtime_noise=0.3, failure_prob=0.1),
    "cvar:0.9:avg_wait")
print("risk-averse picks (S=4, F=64 futures):",
      [pool7.names[int(b)] for b in fan.best])
print("p0 CI half-widths:", np.round(np.asarray(fan.cost_ci)[0], 1))

# --- adaptive fan racing: pay only for open decisions ----------------
# A fixed fan spends S*F*P members even when the winner is obvious.
# Racing (DESIGN.md §11) starts every policy at f0 members, eliminates
# policies whose CI lower bound clears the incumbent's upper bound,
# and doubles survivors' fans up to F_max — CRN prefix-stability means
# each rung replays ONLY the new member suffix (no member is ever
# replayed twice).  Same winners as the full fan; a fraction of the
# replays.  budget_ms/max_members make it anytime.
# CLI: twin_loop --fan 64 --race --race-f0 4 [--budget-ms 500]
from repro.core.race import RaceSpec, race_grid

race = race_grid(scenarios, pool7.spec,
                 RaceSpec(fan=FanSpec(n=64, runtime_noise=0.3,
                                      failure_prob=0.1), f0=4),
                 "cvar:0.9:avg_wait")
print(f"raced picks ({race.members} of {race.members_full} members, "
      f"{len(race.rungs)} rungs, stopped={race.stopped}):",
      [pool7.names[int(b)] for b in race.best])

# --- the twin: simulation-in-the-loop adaptive scheduling ------------
# ``pool`` takes the sweep grammar (DESIGN.md §5): one what-if fork per
# term/grid point, all drained in ONE batched engine call.  "paper" is
# the §4.1 pool {WFP, FCFS, SJF}; a DRAS-style parameter sweep rides
# the same fork axis, e.g.
#     pool="extended,wfp:a=1..5x5:tau=600..7200x5"   # k=32 forks
#     pool="paper,expf:tau=600,lin:est=1:wait=-0.01" # custom scorers
# ``objective`` is the administrator-configured goal (§3.4, DESIGN.md
# §8) each decision cycle minimizes — "score" (the paper's 4-term
# default), "avg_wait", "0.5*avg_wait+0.5*max_slowdown",
# "min:avg_wait@util>=0.85", ... (see core.objective.parse_objective;
# CLI: python -m repro.launch.twin_loop --objective avg_wait)
bus = EventBus()
emulator = ClusterEmulator(trace, total_nodes=32, bus=bus)
twin = SchedTwin(bus=bus,
                 qrun=emulator.qrun,              # §3.5 decision feedback
                 total_nodes=32,
                 max_jobs=emulator.max_jobs,
                 pool="paper",
                 objective="score",               # the paper's goal
                 free_nodes_probe=lambda: emulator.free_nodes)  # §3.2
report = emulator.run(on_event=twin.pump)         # ①→⑦ loop per event
per_policy["SchedTwin"] = report.metric_dict()

# --- resilience: chaos, deadline guard, crash-safe snapshots ---------
# A real event stream drops, duplicates, reorders, and corrupts.
# ChaosBus (DESIGN.md §12) injects every fault class into the twin's
# READ view only — each fault a pure function of (seed, event seq), so
# runs are reproducible — while the twin quarantines garbage into
# dead_letters, absorbs duplicates idempotently, resyncs on loss, and
# the deadline guard (guard=budget_s) degrades the decision down a
# ladder instead of ever missing a cycle.  snapshot()/restore() make
# the whole runtime crash-safe: a fresh twin resumes bitwise.
# CLI: twin_loop --chaos --budget-s 1.0 --snapshot-dir CK [--resume]
from repro.cluster.chaos import DEFAULT_PROFILE, ChaosBus

bus = EventBus()
emulator = ClusterEmulator(trace, total_nodes=32, bus=bus)
view = ChaosBus(bus, DEFAULT_PROFILE)              # chaos on reads only
twin2 = SchedTwin(bus=view, qrun=emulator.qrun, total_nodes=32,
                  max_jobs=emulator.max_jobs, guard=1.0,
                  free_nodes_probe=lambda: emulator.free_nodes,
                  jobs_probe=emulator.jobs_view)    # loss -> resync
report2 = emulator.run(on_event=twin2.pump, on_quiesce=twin2.flush)
stats = twin2.telemetry.resilience_stats()
print(f"\nchaos survival: {report2.n_jobs} jobs, "
      f"injected={dict(view.stats)}")
print(f"quarantined={stats['quarantined']} resyncs={stats['resyncs']} "
      f"miss_rate={stats['miss_rate']:.3f} "
      f"ladder_engaged={stats['ladder_engaged']}")

# --- train, then deploy: closing the θ loop --------------------------
# The twin so far SELECTS among fixed policies; repro.learn SEARCHES θ
# itself (DESIGN.md §13).  A CEM/ES population of candidate parameter
# vectors rides the same fork axis — one replay grid per generation —
# warm-started from the static fixed points and gated on held-out
# scenarios.  The checkpoint then deploys through the pool grammar:
# ``trained:<ckpt>`` is just another term.  Full walkthrough:
# examples/train_policy.py; CLI:
#     twin_loop --train 12 --train-dir CK --objective avg_wait
#     twin_loop --pool trained:CK,paper
from repro.cluster.workload import split_scenarios
from repro.learn import TrainConfig, train

rng = np.random.default_rng(0)
tr, held = split_scenarios(rng, lambda r: paper_synthetic_trace(rng=r),
                           n_train=3, n_heldout=2, total_nodes=32)
res = train(tr, held, TrainConfig(family="lin", population=8,
                                  generations=4,
                                  objective="avg_wait", seed=0),
            engine=DrainEngine())
print(f"\ntrained {res.best_desc}: held-out {res.best_heldout:.1f} "
      f"({res.generations_run} generations)")

# --- Figure-3-style comparison ----------------------------------------
areas = radar_report(per_policy)
print(f"{'method':10s} {'radar area':>10s} {'avg wait':>9s} "
      f"{'max wait':>9s} {'util':>6s}")
for name, m in per_policy.items():
    print(f"{name:10s} {areas[name]:10.2f} {m['avg_wait']:9.1f} "
          f"{m['max_wait']:9.1f} {m['utilization']:6.3f}")
print("\npolicy mix (Table 1):",
      twin.telemetry.policy_start_distribution())
lat = twin.telemetry.cycle_latency_stats()
print(f"cycle latency: p50 {lat['p50_s'] * 1e3:.1f} ms, p95 "
      f"{lat['p95_s'] * 1e3:.1f} ms over {lat['n']} cycles; stage p50 ms",
      {name: round(s * 1e3, 3) for name, s in lat["stage_p50_s"].items()})
