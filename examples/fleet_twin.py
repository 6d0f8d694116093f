"""SchedTwin as a TPU-fleet scheduler (the framework tie-in).

The twin is architecture-agnostic: jobs here are training / prefill /
decode workloads of the 10 assigned architectures, with pod footprints
from ``cluster.workload.arch_job_mix``.  A 32-pod fleet (8192 chips at
256/pod) is scheduled adaptively, with a pod-failure event mid-run —
the twin replans from the NODEFAIL event, victims restart, everything
completes.

    PYTHONPATH=src python examples/fleet_twin.py
"""
from __future__ import annotations

import numpy as np

from repro.cluster.emulator import ClusterEmulator, FailureSpec
from repro.cluster.workload import arch_job_mix
from repro.core.events import EventBus
from repro.core.policies import EXTENDED_POOL
from repro.core.twin import SchedTwin

TOTAL_PODS = 32       # 32 pods x 256 chips = 8192 chips

jobs = arch_job_mix(n_jobs=120, total_pods=TOTAL_PODS, seed=1,
                    mean_gap=25.0)
print(f"fleet workload: {len(jobs)} jobs over {TOTAL_PODS} pods")
by_class = {}
for j in jobs:
    by_class[j.tag.split(':')[1]] = by_class.get(j.tag.split(':')[1], 0) + 1
print("  job classes:", by_class)

failures = [FailureSpec(time=900.0, nodes=4, duration=600.0)]  # 4 pods drop

bus = EventBus()
emulator = ClusterEmulator(jobs, TOTAL_PODS, bus=bus, failures=failures,
                           check_invariants=True)
twin = SchedTwin(bus=bus, qrun=emulator.qrun, total_nodes=TOTAL_PODS,
                 max_jobs=emulator.max_jobs,
                 pool=EXTENDED_POOL,            # wider pool than the paper
                 free_nodes_probe=lambda: emulator.free_nodes,
                 ensemble=4, ensemble_noise=0.3)  # runtime-uncertainty
report = emulator.run(on_event=twin.pump)

print(f"\ncompleted {report.n_jobs} jobs, {report.n_restarts} restarted "
      f"after the pod failure")
print(f"avg wait {report.avg_wait:8.1f} s   max wait {report.max_wait:8.1f} s")
print(f"avg slowdown {report.avg_slowdown:5.2f}   utilization "
      f"{report.utilization:.3f}")
print("policy mix:", {k: f"{v:.0f}%" for k, v in
                      twin.telemetry.policy_start_distribution().items()})
lat = twin.telemetry.cycle_latency_stats()
print(f"decision latency p50 {lat['p50_s'] * 1e3:.1f} ms, p95 "
      f"{lat['p95_s'] * 1e3:.1f} ms over {lat['n']} cycles "
      f"(paper: 'a few seconds')")
