#!/usr/bin/env python3
"""Chip smoke test: the twin's main path, once, on a TPU.

    python chip_smoke.py               # one chip: twin + grid phases
    python chip_smoke.py --four-chips  # the sharded fleet grid, alone

One process holds the chip and runs every phase through the normal
entry points.  Any failed check raises and exits nonzero; nothing is
caught and carried on from.

  twin   The paper's §4.1 deployment: ``paper_synthetic_trace(seed=0)``,
         150 jobs on 32 nodes, J=256 slots, co-simulated as
         ``repro.launch.twin_loop`` does (``ClusterEmulator`` +
         ``EventBus`` + ``SchedTwin``) with ``DrainEngine("pallas")``
         under the ``paper`` pool and the 32-fork ``DRAS_SWEEP_POOL``.
         Each run must end with no dead letters, no bus callback
         failures and every job finished.  The paper-pool run is
         repeated with ``DrainEngine("reference")``: its metric table
         and policy mix must equal the pallas run's.
  grid   One replay grid: 64 paper-trace scenarios x the sweep pool =
         2,048 forks at J=256, backend pallas.  The compiled replay's
         HLO must hold ``tpu_custom_call`` (the Pallas pass compiled,
         neither interpreted nor replaced), and 4 scenarios x the 7
         ``extended`` statics must equal the host event-loop oracle
         (``ClusterEmulator.run(policy_id=...)``, reference backend)
         bit for bit.
  four-chips  (``--four-chips`` only, and then alone)
         ``whatif.sharded_replay_grid`` over ``make_fleet_mesh(4)``,
         block-streamed, on the same grid, equal bit for bit to
         ``engine.replay_grid`` on one device of the same process.

Lines before the last report each phase's wall time, compile time and
peak device memory, as information.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
There is no CPU path: without a TPU the script exits nonzero at the
device gate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NODES = 32            # the paper's §4.1 cluster
GRID_SCENARIOS = 64   # x 32 sweep forks = 2,048 forks
ORACLE_SCENARIOS = 4
FLEET_CHIPS = 4
FLEET_BLOCK = 16      # scenarios per streamed fleet block


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_gate():
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {d.platform!r}); "
                 f"this script has no CPU path")
    print(f"device: {d.device_kind} x{len(devs)} ({d.platform})",
          flush=True)
    return devs


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def cosim(trace, nodes: int, pool, engine):
    """One ``twin_loop`` co-simulation; returns (report, twin)."""
    from repro.cluster.emulator import ClusterEmulator
    from repro.core.events import EventBus
    from repro.core.twin import SchedTwin

    bus = EventBus()
    em = ClusterEmulator(trace, nodes, bus=bus, check_invariants=True,
                         engine=engine)
    twin = SchedTwin(bus=bus, qrun=em.qrun, total_nodes=nodes,
                     max_jobs=em.max_jobs, pool=pool,
                     free_nodes_probe=lambda: em.free_nodes,
                     jobs_probe=em.jobs_view, engine=engine)
    report = em.run(on_event=twin.pump, on_quiesce=twin.flush)
    failures = bus.health()["callback_failures"]
    check(not twin.dead_letters,
          f"{len(twin.dead_letters)} dead letters, first: "
          f"{twin.dead_letters[0].reason if twin.dead_letters else ''}")
    check(failures == 0, f"{failures} bus callback failures")
    n = len(trace)
    check(report.n_jobs == n and bool(np.all(report.start_t >= 0))
          and bool(np.all(report.end_t >= report.start_t)),
          "not every job finished")
    return report, twin


def twin_phase(trace, nodes: int, dev) -> None:
    from repro.configs.schedtwin import DRAS_SWEEP_POOL
    from repro.core.engine import DrainEngine
    from repro.core.policies import parse_pool

    pallas, reference = DrainEngine("pallas"), DrainEngine("reference")
    runs = {}
    for name, pool, engine in (("paper/pallas", "paper", pallas),
                               ("sweep/pallas", DRAS_SWEEP_POOL, pallas),
                               ("paper/reference", "paper", reference)):
        t0 = time.perf_counter()
        report, twin = cosim(trace, nodes, parse_pool(pool), engine)
        lat = twin.telemetry.cycle_latency_stats()
        runs[name] = (report.metric_dict(),
                      twin.telemetry.policy_start_distribution())
        print(f"twin {name}: {report.n_jobs} jobs, {lat['n']} cycles, "
              f"wall {time.perf_counter() - t0:.3f} s, cycle p50 "
              f"{lat['p50_s'] * 1e3:.3f} ms, p95 "
              f"{lat['p95_s'] * 1e3:.3f} ms, peak_bytes_in_use "
              f"{peak_bytes(dev)}", flush=True)
        print(f"  metrics {runs[name][0]}", flush=True)
        print(f"  policy mix {runs[name][1]}", flush=True)
    check(runs["paper/pallas"] == runs["paper/reference"],
          "pallas and reference twins disagree on the paper pool")


def grid_phase(cfg, dev, oracle_scenarios: int) -> None:
    import jax

    from repro.cluster.emulator import ClusterEmulator
    from repro.core.engine import DrainEngine
    from repro.core.policies import parse_pool

    engine = cfg.make_engine()
    pool = cfg.make_pool()
    statics = parse_pool("extended")
    check(pool.names[:len(statics)] == statics.names,
          "the grid pool does not lead with the extended statics")
    traces = cfg.make_traces()
    scen = cfg.make_scenarios()

    t0 = time.perf_counter()
    hlo = engine.lower_replay_grid(scen, pool.spec).compile().as_text()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in hlo,
          "no tpu_custom_call in the compiled replay: the Pallas pass "
          "was not compiled")

    t0 = time.perf_counter()
    out = engine.replay_grid(scen, pool.spec)
    jax.block_until_ready(out.end_t)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = engine.replay_grid(scen, pool.spec)
    jax.block_until_ready(out.end_t)
    warm_s = time.perf_counter() - t0
    S, P, J = out.start_t.shape
    print(f"grid: S={S} x P={P} = {S * P} forks at J={J}, compile "
          f"{compile_s:.3f} s, first call {first_s:.3f} s, second call "
          f"{warm_s:.3f} s, peak_bytes_in_use {peak_bytes(dev)}",
          flush=True)
    check((S, P) == (cfg.scenarios, len(pool)), "grid shape")
    check(not bool(np.asarray(out.deadlocked).any()), "deadlocked forks")
    for name, field in out.metrics._asdict().items():
        check(bool(np.isfinite(np.asarray(field)).all()),
              f"non-finite grid metric {name}")

    t0 = time.perf_counter()
    oracle = DrainEngine("reference")
    start, end = np.asarray(out.start_t), np.asarray(out.end_t)
    for s in range(oracle_scenarios):
        n = len(traces[s])
        for p in range(len(statics)):
            rep = ClusterEmulator(traces[s], cfg.total_nodes,
                                  engine=oracle).run(policy_id=pool.fork(p))
            check(np.array_equal(start[s, p, :n],
                                 rep.start_t.astype(np.float32))
                  and np.array_equal(end[s, p, :n],
                                     rep.end_t.astype(np.float32)),
                  f"grid != host oracle at scenario {s}, "
                  f"{pool.names[p]}")
    print(f"grid oracle: {oracle_scenarios} scenarios x {len(statics)} "
          f"statics bit-identical to the host event loop "
          f"({time.perf_counter() - t0:.3f} s)", flush=True)


def four_chip_phase(cfg, devs, block: int) -> None:
    import jax

    from repro.core.whatif import sharded_replay_grid
    from repro.launch.mesh import make_fleet_mesh

    engine = cfg.make_engine()
    pool = cfg.make_pool()
    scen = cfg.make_scenarios()
    run = sharded_replay_grid(make_fleet_mesh(FLEET_CHIPS), engine=engine,
                              block_size=block)
    t0 = time.perf_counter()
    fleet = run(scen, pool.spec)
    jax.block_until_ready(fleet.end_t)
    fleet_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    local = engine.replay_grid(scen, pool.spec)
    jax.block_until_ready(local.end_t)
    local_s = time.perf_counter() - t0
    S, P, J = local.start_t.shape
    print(f"fleet: S={S} x P={P} forks at J={J} over {FLEET_CHIPS} chips "
          f"in blocks of {block} scenarios: {fleet_s:.3f} s (first call); "
          f"one device: {local_s:.3f} s (first call); peak_bytes_in_use "
          f"{[peak_bytes(d) for d in devs[:FLEET_CHIPS]]}", flush=True)
    pairs = {"start_t": (fleet.start_t, local.start_t),
             "end_t": (fleet.end_t, local.end_t),
             "deadlocked": (fleet.deadlocked, local.deadlocked),
             "events": (fleet.events, local.events),
             "costs": (fleet.costs, local.costs),
             "best": (fleet.best, local.best)}
    for name in local.metrics._fields:
        pairs[f"metrics.{name}"] = (getattr(fleet.metrics, name),
                                    getattr(local.metrics, name))
    for name, (a, b) in pairs.items():
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"sharded grid != one-device grid in {name}")
    print(f"fleet: sharded grid bit-identical to the one-device grid "
          f"({len(pairs)} fields)", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet grid on 4 chips "
                         "and its one-device comparison")
    args = ap.parse_args(argv)
    devs = device_gate()

    from repro.cluster.workload import paper_synthetic_trace
    from repro.configs.schedtwin import DRAS_SWEEP_POOL, ReplayGridConfig
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    grid = ReplayGridConfig(scenarios=GRID_SCENARIOS, trace="paper",
                            total_nodes=NODES, pool=DRAS_SWEEP_POOL,
                            backend="pallas")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(grid, devs, FLEET_BLOCK)
    else:
        twin_phase(paper_synthetic_trace(seed=0), NODES, devs[0])
        grid_phase(grid, devs[0], ORACLE_SCENARIOS)
    print(f"all phases: {time.perf_counter() - t0:.3f} s", flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
