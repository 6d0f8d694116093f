"""The paper's loop as a CLI: twin + PBS-emulator co-simulation.

    python -m repro.launch.twin_loop                  # paper §4.1 setup
    python -m repro.launch.twin_loop --pool extended --ensemble 8
    python -m repro.launch.twin_loop --failures 2     # fault injection
    python -m repro.launch.twin_loop --backend pallas # kernel what-ifs
    python -m repro.launch.twin_loop --trace bursty   # diurnal arrivals
    python -m repro.launch.twin_loop --trace lublin --nodes 560 \
        --jobs 2048 --attach 1536 --window 1024
        # Polaris-scale: the scheduler runs 1,536 submissions on its
        # own, then a twin with a 1,024-slot job table attaches
    python -m repro.launch.twin_loop --replay-grid 8  # S x P baseline grid
    python -m repro.launch.twin_loop --replay-grid 64 \\
        --shard 0 --block-size 16      # fleet: sharded + block-streamed
    python -m repro.launch.twin_loop --objective avg_wait
    python -m repro.launch.twin_loop \\
        --objective "min:avg_wait@util>=0.85"         # constrained goal
    python -m repro.launch.twin_loop --fan 64 --fan-noise 0.3 \\
        --objective "p95:avg_wait"    # Monte-Carlo fan, tail objective
    python -m repro.launch.twin_loop --replay-grid 8 --fan 128 \\
        --fan-fail 0.2 --objective "cvar:0.9:score" --prune
    python -m repro.launch.twin_loop --fan 64 --race --budget-ms 500 \\
        # raced fan: successive-halving to F_max=64, 500 ms anytime cap
    python -m repro.launch.twin_loop --replay-grid 8 --fan 64 --race \\
        --race-f0 4                   # raced S x F x P grid
    python -m repro.launch.twin_loop --train 24 --train-family lin \\
        --train-dir ckpt/policy       # learn θ (DESIGN.md §13) ...
    python -m repro.launch.twin_loop --pool trained:ckpt/policy,paper \\
        # ... then deploy it live, statics riding as the safety floor

``--objective`` is the administrator-configured optimization goal
(§3.4; ``repro.core.objective``, DESIGN.md §8): the goal grammar is
validated (parse -> spec -> parse round-trip) and the resolved goal is
logged at startup.  In twin mode it drives every decision cycle; in
``--replay-grid`` mode it drives the per-scenario policy selection.

``--attach N`` lets the emulator's own scheduler (FCFS + EASY) run the
stream through submission N before the twin attaches from its job view
(``SchedTwin.attach``), as a twin started beside a running PBS server
would; ``--window W`` gives the twin's job table W slots (default: the
emulator's, the whole stream), recycled as jobs end.

``--replay-grid S`` skips the co-simulation and instead evaluates the
full (S scenarios × pool) baseline grid in ONE batched device replay
(``engine.replay_grid``, DESIGN.md §6), printing per-policy metrics
aggregated over scenarios.

``--race`` turns the fixed-F fan into a successive-halving race
(DESIGN.md §11): every policy starts at ``--race-f0`` members,
per-rung CIs eliminate statistically-dominated policies, survivors
double their fan up to ``--fan`` (= F_max), and CRN prefix-stability
means each rung replays only the new member suffix.  ``--budget-ms`` /
``--race-members`` make the race anytime.  Works in twin mode
(``SchedTwin(race=...)``) and in ``--replay-grid`` mode (including
sharded/block-streamed via ``--shard``/``--block-size``).

``--fan F`` evaluates every policy over an on-device Monte-Carlo fan
of F perturbed futures (DESIGN.md §10) — runtime noise
(``--fan-noise``), arrival-burst warps (``--fan-burst``), node-failure
draws (``--fan-fail``), deterministically keyed by ``--fan-seed``.
One base scenario is uploaded; the fan is expanded inside the jit, so
H2D traffic stays O(1) in F.  In twin mode decisions gain
device-computed confidence intervals (logged per cycle); in
``--replay-grid`` mode the grid becomes S × F × P and ``--prune``
turns on the goal-conditioned low-F pre-pass that drops dominated
policies before the full fan.

``--train G`` runs the on-device policy-learning loop (``repro.learn``,
DESIGN.md §13) for G generations instead of the co-simulation: each
generation's candidate θ population rides the fork axis of ONE batched
replay grid over training scenarios split deterministically from the
held-out set (``workload.split_scenarios``), scored by ``--objective``
(with ``--fan*`` flags domain-randomizing the training traces).  The
incumbent checkpoints to ``--train-dir`` and deploys via
``--pool trained:<dir>``; the final report scores it against the
``--pool`` statics on the held-out scenarios.  ``--resume`` continues
a training run from its latest checkpoint, bitwise.

``--pool`` takes the sweep grammar (``repro.core.policies.parse_pool``):
one fork per grid point, e.g. a DRAS-style 25-point parameter sweep
riding with the 7 static policies (k=32 forks, ONE batched drain):

    python -m repro.launch.twin_loop \\
        --pool "extended,wfp:a=1..5x5:tau=600..7200x5"
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.cluster.emulator import ClusterEmulator, FailureSpec
from repro.cluster.workload import (bursty_trace, lublin_trace,
                                    paper_synthetic_trace, poisson_trace)
from repro.core.engine import PASS_BACKENDS, DrainEngine
from repro.core.events import EventBus
from repro.core.fan import FanSpec
from repro.core.objective import Objective, validate_objective
from repro.core.policies import parse_pool
from repro.core.twin import SchedTwin


def resolve_objective(grammar: str) -> Objective:
    """Parse ``--objective`` with round-trip validation
    (``objective.validate_objective``), CLI-fatal on failure."""
    try:
        return validate_objective(grammar)
    except ValueError as e:
        raise SystemExit(str(e))


def make_fan(args) -> "FanSpec | None":
    """Build the ``FanSpec`` from the --fan* flags (None when off)."""
    if not args.fan:
        return None
    return FanSpec(n=args.fan, runtime_noise=args.fan_noise,
                   burst_amplitude=args.fan_burst,
                   failure_prob=args.fan_fail, seed=args.fan_seed)


def make_race(args):
    """Build the ``RaceSpec`` from --race/--race-f0/--budget-ms/
    --race-members over the --fan* spec (None when --race is off)."""
    if not args.race:
        return None
    from repro.core.race import RaceSpec
    return RaceSpec(fan=make_fan(args), f0=args.race_f0,
                    budget_ms=args.budget_ms or None,
                    max_members=args.race_members or None)


def raced_grid(args, engine, goal, pool, scen) -> None:
    """--replay-grid --race: the raced S × F × P grid.  Eliminated
    policies never reach full fidelity, so the report is the race
    ledger (rungs, members, separation), not the per-policy metric
    table a full grid prints."""
    import time

    race = make_race(args)
    fleet = args.shard != 1 or args.block_size
    t0 = time.perf_counter()
    if fleet:
        from repro.core.whatif import sharded_race_grid
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(None if args.shard == 0 else args.shard)
        run = sharded_race_grid(mesh, engine=engine, objective=goal,
                                race=race,
                                block_size=args.block_size or None)
        out = run(scen, pool.spec)
        mode = (f"{mesh.shape['data']} shard(s), "
                f"block={args.block_size or 'whole rung'}")
    else:
        from repro.core.race import race_grid
        out = race_grid(scen, pool.spec, race, goal, engine=engine)
        mode = "one device per rung"
    wall = time.perf_counter() - t0
    S = int(out.costs.shape[0])
    print(f"raced grid: S={S} scenarios x F_max={race.f_max} x "
          f"P={len(pool)} policies ({mode}) in {wall:.2f}s")
    print(f"members: {out.members} of {out.members_full} fixed-F "
          f"({out.members_full / max(out.members, 1):.1f}x reduction), "
          f"{len(out.rungs)} rungs, stopped={out.stopped}")
    for r in out.rungs:
        el = ([pool.names[i] for i in r.eliminated]
              if r.eliminated else "-")
        print(f"  rung [{r.lo:3d},{r.hi:3d}) x {len(r.active)} "
              f"policies: {r.members} members, sep={r.separation:+.2f}, "
              f"eliminated {el}")
    names = [pool.names[int(i)] for i in out.keep]
    best = np.asarray(out.best)
    print(f"survivors at F={out.fan_size}: {names}")
    print(f"objective {goal}: per-scenario winners "
          f"{[pool.names[int(b)] for b in best]}")


def replay_grid(args, engine: DrainEngine, goal: Objective) -> None:
    """--replay-grid: the S × P baseline grid as ONE device replay,
    with the per-scenario policy selection under ``goal`` (S × F × P
    with --fan: every policy judged over F perturbed futures)."""
    import time

    from repro.configs.schedtwin import ReplayGridConfig

    cfg = ReplayGridConfig(scenarios=args.replay_grid, trace=args.trace,
                           n_jobs=args.jobs, total_nodes=args.nodes,
                           pool=args.pool, objective=goal, seed=args.seed,
                           backend=engine.backend)
    pool = cfg.make_pool()
    scen = cfg.make_scenarios()
    if args.race:
        return raced_grid(args, engine, cfg.make_objective(), pool, scen)
    fan = make_fan(args)
    fleet = args.shard != 1 or args.block_size
    prune_info = None
    if fleet:
        # the fleet engine: scenario axis sharded over the mesh and/or
        # streamed in fixed-size blocks (whatif.sharded_replay_grid /
        # sharded_fan_grid, DESIGN.md §§9–10)
        from repro.core.whatif import sharded_fan_grid, sharded_replay_grid
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(None if args.shard == 0 else args.shard)
        if fan is not None:
            run = sharded_fan_grid(mesh, engine=engine,
                                   objective=cfg.make_objective(), fan=fan,
                                   block_size=args.block_size or None)
        else:
            run = sharded_replay_grid(mesh, engine=engine,
                                      objective=cfg.make_objective(),
                                      block_size=args.block_size or None,
                                      prefetch_depth=args.prefetch)
        mode = (f"{mesh.shape['data']} shard(s), "
                f"block={args.block_size or 'whole set'}, "
                f"prefetch={args.prefetch}")
    t0 = time.perf_counter()
    if fleet:
        out = run(scen, pool.spec)
    elif fan is not None and args.prune:
        from repro.core.fan import pruned_fan_grid
        out, prune_info = pruned_fan_grid(scen, pool.spec, fan,
                                          cfg.make_objective(),
                                          engine=engine)
        mode = "one device computation, pruned"
    elif fan is not None:
        out = engine.fan_grid(scen, pool.spec, fan, cfg.make_objective())
        mode = "one device computation"
    else:
        out = engine.replay_grid(scen, pool.spec, cfg.make_objective())
        mode = "one device computation"
    np.asarray(out.end_t)  # block
    wall = time.perf_counter() - t0
    S = int(out.deadlocked.shape[0])
    P = int(out.deadlocked.shape[-1])
    fan_txt = (f" x F={fan.n} fan members" if fan is not None else "")
    print(f"replay grid: S={S} scenarios{fan_txt} x P={P} policies "
          f"({int(np.prod(out.deadlocked.shape))} forks, {mode}) "
          f"in {wall:.2f}s")
    if prune_info is not None:
        kept = [pool.names[int(i)] for i in np.asarray(prune_info.keep)]
        print(f"prune: pre-pass F={prune_info.pre_members.shape[1]} "
              f"dropped {prune_info.rate * 100:.0f}% of the pool; "
              f"kept {kept}")
    print(f"{'policy':>16s} {'avg_wait':>9s} {'max_wait':>9s} "
          f"{'avg_sd':>7s} {'util':>6s} {'dead':>5s} {'picked':>7s}")
    m = out.metrics                 # (S, P), or (S, F, P) under --fan
    names = pool.names if prune_info is None \
        else [pool.names[int(i)] for i in np.asarray(prune_info.keep)]
    # per-scenario selection; sub-pool indexed when pruned (matches
    # ``names`` either way)
    best = np.asarray(out.best)
    for p, name in enumerate(names):
        print(f"{name:>16s} "
              f"{float(np.mean(np.asarray(m.avg_wait).reshape(-1, len(names))[:, p])):9.1f} "
              f"{float(np.mean(np.asarray(m.max_wait).reshape(-1, len(names))[:, p])):9.1f} "
              f"{float(np.mean(np.asarray(m.avg_slowdown).reshape(-1, len(names))[:, p])):7.2f} "
              f"{float(np.mean(np.asarray(m.utilization).reshape(-1, len(names))[:, p])):6.3f} "
              f"{int(np.asarray(out.deadlocked).reshape(-1, len(names))[:, p].sum()):5d} "
              f"{int((best == p).sum()):4d}/{S}")
    if fan is not None:
        # device-computed per-policy uncertainty, scenario-averaged
        ci = np.asarray(out.cost_ci)
        wd = np.asarray(out.fan_width)
        parts = " ".join(
            f"{n}={np.mean(ci[:, p]):.2f}±w{np.mean(wd[:, p]):.1f}"
            for p, n in enumerate(names))
        print(f"fan confidence (mean 95% CI half-width ± member "
              f"spread): {parts}")
    print(f"objective {goal}: per-scenario winners "
          f"{[names[int(b)] for b in best]}")


def train_mode(args, engine: DrainEngine, goal: Objective,
               floor_pool) -> None:
    """--train: the repro.learn loop — train θ on a deterministic
    scenario split, checkpoint to --train-dir, then score the incumbent
    against the --pool statics on the held-out scenarios (the same
    comparison ``--pool trained:<dir>,<statics>`` deploys live)."""
    import time

    from repro.cluster.workload import split_scenarios
    from repro.learn import TrainConfig, train

    rng = np.random.default_rng(args.seed)
    if args.trace == "paper":
        trace_fn = lambda r: paper_synthetic_trace(rng=r)
    elif args.trace == "bursty":
        trace_fn = lambda r: bursty_trace(
            args.jobs, args.nodes, 8.0, (1, args.nodes), (30.0, 900.0),
            rng=r)
    else:
        trace_fn = lambda r: poisson_trace(
            args.jobs, args.nodes, 8.0, (1, args.nodes), (30.0, 900.0),
            rng=r)
    train_scen, heldout = split_scenarios(
        rng, trace_fn, args.train_scenarios, args.train_heldout,
        args.nodes)
    cfg = TrainConfig(family=args.train_family,
                      strategy=args.train_strategy,
                      population=args.train_pop, generations=args.train,
                      objective=goal, seed=args.seed, fan=make_fan(args))
    print(f"train: {cfg.strategy}/{cfg.family} pop={cfg.population} x "
          f"{args.train_scenarios} train scenarios "
          f"(+{args.train_heldout} held-out), goal {goal}")
    t0 = time.perf_counter()
    res = train(train_scen, heldout, cfg, engine=engine,
                checkpoint_dir=args.train_dir or None,
                resume=args.resume, log_fn=print)
    wall = time.perf_counter() - t0
    print(f"trained {res.generations_run} generations in {wall:.1f}s"
          f"{' (early stop)' if res.stopped_early else ''}: "
          f"{res.best_desc}")

    # held-out scoreboard: incumbent + the --pool statics in ONE grid
    # (within-pool, so rank-based goals compare apples to apples)
    board = res.pool + floor_pool
    costs = np.asarray(engine.generation_costs(heldout, board.spec, goal),
                       np.float64)
    agg = costs.mean(axis=0)
    print(f"{'policy':>16s} {'held-out cost':>14s}")
    for p, name in enumerate(board.names):
        mark = " <- trained" if p == 0 else ""
        print(f"{name:>16s} {agg[p]:14.4f}{mark}")
    if args.train_dir:
        print(f"deploy: --pool trained:{args.train_dir}"
              f"{',' + args.pool if args.pool else ''}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", choices=("paper", "poisson", "bursty",
                                        "lublin"),
                    default="paper")
    ap.add_argument("--attach", type=int, default=0, metavar="N",
                    help="run the stream's first N submissions under the "
                         "emulator's own FCFS + EASY scheduler, then "
                         "attach the twin from its job view")
    ap.add_argument("--window", type=int, default=0, metavar="W",
                    help="the twin's job table: W slots for live jobs, "
                         "recycled as jobs end (default: the emulator's "
                         "table, the whole stream)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="skip the persistent XLA compilation cache "
                         "(default: cache compiled engines under "
                         "$JAX_COMPILATION_CACHE_DIR, else <repo>/"
                         ".jax_cache, so compiles are paid once per "
                         "machine)")
    ap.add_argument("--jobs", type=int, default=150)
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--pool", default="paper",
                    help="pool grammar: comma-separated policy terms, "
                         "optionally swept, e.g. 'paper', 'extended', "
                         "'wfp,fcfs,sjf,wfp:a=1..5x5' (see "
                         "policies.parse_pool)")
    ap.add_argument("--objective", default="score",
                    help="optimization goal grammar (core.objective."
                         "parse_objective): 'score' (paper default), "
                         "'avg_wait', '0.5*avg_wait+0.5*max_slowdown', "
                         "'lex:avg_wait,makespan', "
                         "'min:avg_wait@util>=0.85'")
    ap.add_argument("--ensemble", type=int, default=1)
    ap.add_argument("--fan", type=int, default=0, metavar="F",
                    help="decide over an on-device Monte-Carlo fan of F "
                         "perturbed futures per policy (DESIGN.md §10); "
                         "works in twin mode and with --replay-grid")
    ap.add_argument("--fan-noise", type=float, default=0.3,
                    help="lognormal runtime-noise sigma for fan members "
                         "(mean-preserving; member 0 stays exact)")
    ap.add_argument("--fan-burst", type=float, default=0.0,
                    help="arrival-burst warp amplitude in [0,1) for fan "
                         "members (replay mode only — a drain has no "
                         "future arrivals)")
    ap.add_argument("--fan-fail", type=float, default=0.0,
                    help="per-member node-failure probability; a hit "
                         "member loses a random fraction of the cluster")
    ap.add_argument("--fan-seed", type=int, default=0,
                    help="fan PRNG seed (member draws are keyed per "
                         "(scenario, member) — deterministic, resumable)")
    ap.add_argument("--race", action="store_true",
                    help="race the --fan via successive halving "
                         "(DESIGN.md §11): start every policy at "
                         "--race-f0 members, CI-eliminate dominated "
                         "policies per rung, double survivors' fans up "
                         "to --fan; prefix-stable CRN means no member "
                         "is ever replayed twice")
    ap.add_argument("--race-f0", type=int, default=8, metavar="F0",
                    help="rung-0 fan size for --race (default 8)")
    ap.add_argument("--budget-ms", type=float, default=0.0, metavar="MS",
                    help="anytime wall-clock budget per race; when it "
                         "runs out mid-race the current best is "
                         "returned with its achieved confidence")
    ap.add_argument("--race-members", type=int, default=0, metavar="M",
                    help="anytime (scenario, member, policy) triple "
                         "budget per race")
    ap.add_argument("--prune", action="store_true",
                    help="goal-conditioned pool pruning for --replay-grid "
                         "--fan: a cheap low-F pre-pass drops policies "
                         "the objective provably never selects, then the "
                         "full fan runs on the survivors")
    ap.add_argument("--failures", type=int, default=0)
    ap.add_argument("--budget-s", type=float, default=0.0, metavar="S",
                    help="wall-clock budget per decision cycle "
                         "(guard.DeadlineGuard, DESIGN.md §12): under "
                         "pressure the twin degrades down the ladder "
                         "(shrunk race/fan -> static pool -> hold "
                         "incumbent) instead of deciding late")
    ap.add_argument("--chaos", action="store_true",
                    help="read the bus through cluster.chaos.ChaosBus "
                         "with the default fault profile (drops, dups, "
                         "reordering, corruption, transient read "
                         "failures) — the hardened ingestion layer must "
                         "absorb all of it")
    ap.add_argument("--snapshot-dir", default="", metavar="DIR",
                    help="persist crash-safe twin snapshots (SimState + "
                         "consumer offset + RNG key + telemetry + "
                         "emulator/bus state) under DIR via "
                         "checkpoint.CheckpointManager")
    ap.add_argument("--snapshot-every", type=int, default=25, metavar="N",
                    help="snapshot every N decision cycles (with "
                         "--snapshot-dir; default 25)")
    ap.add_argument("--kill-after-cycle", type=int, default=0, metavar="K",
                    help="simulate a crash: snapshot and hard-exit after "
                         "decision cycle K (requires --snapshot-dir); "
                         "rerun with --resume to continue")
    ap.add_argument("--resume", action="store_true",
                    help="resume the co-simulation from the latest "
                         "snapshot in --snapshot-dir (same flags as the "
                         "original run)")
    ap.add_argument("--backend",
                    choices=sorted(PASS_BACKENDS) + ["auto"],
                    default="auto",
                    help="scheduling-pass backend for the what-if engine "
                         "(auto: reference on CPU, pallas on TPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train", type=int, default=0, metavar="G",
                    help="train θ for G generations (repro.learn, "
                         "DESIGN.md §13) instead of running the twin: "
                         "each generation is ONE batched replay grid "
                         "with the candidate population on the fork "
                         "axis, scored by --objective")
    ap.add_argument("--train-family", choices=("lin", "wfp", "expf"),
                    default="lin",
                    help="policy family whose θ is searched (lin: "
                         "linear feature scorer; wfp/expf: the "
                         "parametric aging families)")
    ap.add_argument("--train-strategy", choices=("cem", "es"),
                    default="cem",
                    help="search strategy: cross-entropy (cem) or "
                         "OpenAI-style evolution strategy (es)")
    ap.add_argument("--train-pop", type=int, default=16, metavar="N",
                    help="candidate population per generation")
    ap.add_argument("--train-scenarios", type=int, default=8, metavar="S",
                    help="training scenarios (drawn with --trace/--seed "
                         "via workload.split_scenarios)")
    ap.add_argument("--train-heldout", type=int, default=4, metavar="S",
                    help="held-out scenarios for model selection and "
                         "early stopping (disjoint from training by "
                         "construction)")
    ap.add_argument("--train-dir", default="", metavar="DIR",
                    help="checkpoint directory for the trained policy "
                         "(deploy later with --pool trained:DIR); empty "
                         "trains in-memory only")
    ap.add_argument("--replay-grid", type=int, default=0, metavar="S",
                    help="evaluate an S-scenario x pool baseline grid in "
                         "one batched replay instead of running the "
                         "twin co-simulation")
    ap.add_argument("--shard", type=int, default=1, metavar="N",
                    help="shard the --replay-grid scenario axis over N "
                         "devices (0: all local devices) via the fleet "
                         "engine (whatif.sharded_replay_grid)")
    ap.add_argument("--block-size", type=int, default=0, metavar="B",
                    help="stream the --replay-grid in blocks of B "
                         "scenarios per device step (0: one shot); "
                         "bounds device memory at fleet scale")
    ap.add_argument("--prefetch", type=int, default=2, metavar="D",
                    help="host-side ingestion lookahead for block "
                         "streaming (0: ingest inline, no overlap)")
    args = ap.parse_args()
    if (args.shard != 1 or args.block_size or args.prefetch != 2) \
            and not args.replay_grid:
        ap.error("--shard/--block-size/--prefetch apply to --replay-grid")
    if args.replay_grid and (args.failures or args.ensemble > 1):
        ap.error("--replay-grid evaluates static baselines; --failures "
                 "and --ensemble do not apply (run the co-simulation "
                 "for those)")
    if args.fan and args.ensemble > 1:
        ap.error("--fan and --ensemble are mutually exclusive "
                 "(the fan subsumes the estimate-noise ensemble)")
    if args.prune and not (args.fan and args.replay_grid):
        ap.error("--prune applies to --replay-grid --fan")
    if args.race and not args.fan:
        ap.error("--race needs --fan F (F is the race's F_max)")
    if args.race and args.prune:
        ap.error("--race subsumes --prune (elimination is per rung)")
    if (args.race_f0 != 8 or args.budget_ms or args.race_members) \
            and not args.race:
        ap.error("--race-f0/--budget-ms/--race-members apply to --race")
    if args.replay_grid and (args.chaos or args.snapshot_dir
                             or args.budget_s or args.attach
                             or args.window):
        ap.error("--chaos/--snapshot-dir/--budget-s/--attach/--window "
                 "apply to the twin co-simulation, not --replay-grid")
    if args.attach and args.resume:
        ap.error("--resume continues an attached run from its snapshot; "
                 "drop --attach")
    if args.train:
        if args.replay_grid:
            ap.error("--train and --replay-grid are mutually exclusive")
        if (args.failures or args.ensemble > 1 or args.race
                or args.chaos or args.snapshot_dir or args.budget_s
                or args.prune or args.kill_after_cycle):
            ap.error("--train runs the learning loop; co-simulation and "
                     "racing flags do not apply")
        if args.resume and not args.train_dir:
            ap.error("--train --resume requires --train-dir")
    elif (args.train_dir or args.train_pop != 16
          or args.train_scenarios != 8 or args.train_heldout != 4):
        ap.error("--train-* flags apply to --train G")
    if (args.kill_after_cycle or args.resume) and not (
            args.snapshot_dir or args.train):
        ap.error("--kill-after-cycle/--resume require --snapshot-dir "
                 "(or --train --train-dir)")
    from repro.launch.cache import enable_persistent_cache
    enable_persistent_cache(enabled=not args.no_compile_cache)
    engine = DrainEngine(backend=args.backend)
    pool = parse_pool(args.pool)
    goal = resolve_objective(args.objective)
    print(f"pool: k={len(pool)} forks "
          f"[{', '.join(pool.names[:8])}{', ...' if len(pool) > 8 else ''}] "
          f"backend={engine.backend}")
    print(f"objective: {goal} ({type(goal).__name__})")

    if args.replay_grid:
        return replay_grid(args, engine, goal)
    if args.train:
        return train_mode(args, engine, goal, pool)

    if args.trace == "paper":
        trace = paper_synthetic_trace(seed=args.seed)
    elif args.trace == "bursty":
        trace = bursty_trace(args.jobs, args.nodes, 8.0, (1, args.nodes),
                             (30.0, 900.0), seed=args.seed)
    elif args.trace == "lublin":
        trace = lublin_trace(args.jobs, args.nodes, seed=args.seed)
    else:
        trace = poisson_trace(args.jobs, args.nodes, 8.0, (1, args.nodes),
                              (30.0, 900.0), seed=args.seed)

    rng = np.random.default_rng(args.seed)
    makespan_guess = len(trace) * 8.0
    failures = [FailureSpec(time=float(rng.uniform(0.2, 0.8) * makespan_guess),
                            nodes=max(1, args.nodes // 8),
                            duration=300.0)
                for _ in range(args.failures)]

    bus = EventBus()
    manager = None
    if args.snapshot_dir:
        from repro.checkpoint import CheckpointManager
        manager = CheckpointManager(args.snapshot_dir)
    if args.resume:
        # Peek at the manifest for the persisted bus log BEFORE building
        # the emulator/twin (both need the bus); twin.restore() then
        # re-reads the same step for everything else.
        import json
        import os

        from repro.checkpoint.manager import MANIFEST, step_dir
        step = manager.latest_step()
        if step is None:
            raise SystemExit(f"--resume: no snapshot under "
                             f"{args.snapshot_dir!r}")
        with open(os.path.join(step_dir(args.snapshot_dir, step),
                               MANIFEST)) as f:
            peek = json.load(f).get("extra", {}).get("app", {})
        bus = EventBus.from_dump(peek.get("bus", []))
    em = ClusterEmulator(trace, args.nodes, bus=bus, failures=failures,
                         check_invariants=True, engine=engine)
    race = make_race(args)
    view = bus
    if args.chaos:
        from repro.cluster.chaos import DEFAULT_PROFILE, ChaosBus
        view = ChaosBus(bus, dataclasses.replace(DEFAULT_PROFILE,
                                                 seed=args.seed))
        print(f"chaos: {view.spec}")
    twin = SchedTwin(
        bus=view, qrun=em.qrun, total_nodes=args.nodes,
        max_jobs=args.window or em.max_jobs, pool=pool, objective=goal,
        free_nodes_probe=lambda: em.free_nodes,
        jobs_probe=em.jobs_view, guard=args.budget_s or None,
        ensemble=args.ensemble, fan=None if race else make_fan(args),
        race=race, engine=engine)
    if args.resume:
        step, app = twin.restore(manager)
        em.restore_state(app["emulator"])
        print(f"resumed from snapshot step {step} "
              f"({len(twin.telemetry.cycles)} cycles already decided)")
    if args.attach:
        from repro.core.policies import FCFS
        from repro.core.state import INVALID
        em.advance(lambda: int(np.count_nonzero(em.state != INVALID))
                   >= args.attach, policy_id=FCFS)
        twin.attach()
        print(f"attached at t={em.now:.1f} s after {args.attach} "
              f"submissions: {len(twin.slots)} live jobs in "
              f"{twin.max_jobs} slots")

    def take_snapshot():
        twin.snapshot(manager, app_extra={
            "emulator": em.snapshot_state(), "bus": bus.dump()})

    snap_next = [args.snapshot_every]

    def pump():
        twin.pump()
        cyc = len(twin.telemetry.cycles)
        if manager is not None and cyc >= snap_next[0]:
            take_snapshot()
            snap_next[0] = cyc + args.snapshot_every
        if args.kill_after_cycle and cyc >= args.kill_after_cycle:
            take_snapshot()
            raise SystemExit(
                f"killed after cycle {cyc} (snapshot persisted under "
                f"{args.snapshot_dir!r}; rerun with --resume)")

    report = em.run(on_event=pump, objective=goal,
                    on_quiesce=twin.flush)
    if manager is not None:
        take_snapshot()

    print(f"jobs={report.n_jobs} events={report.n_events} "
          f"restarts={report.n_restarts}")
    for k, v in report.metric_dict().items():
        print(f"  {k:14s} {v:10.2f}")
    if report.objective_cost is not None:
        print(f"objective cost ({report.objective}): "
              f"{report.objective_cost:.3f}")
    else:
        # rank-based goal: a lone run has no scalar cost — show terms
        terms = " ".join(f"{t}={v:.2f}"
                         for t, v in (report.objective_terms or {}).items())
        print(f"objective terms ({report.objective}): {terms}")
    breakdown = twin.telemetry.objective_breakdown()
    for name, terms in breakdown.items():
        parts = " ".join(f"{t}={v:.2f}" for t, v in terms.items())
        print(f"  whatif breakdown {name:>10s}: {parts}")
    print("policy mix:", {k: f"{v:.1f}%" for k, v in
                          twin.telemetry.policy_start_distribution().items()})
    conf = twin.telemetry.confidence_stats()
    if conf:
        # device-computed fan uncertainty (decide_fan / decide_race
        # stamps; DESIGN.md §§10–11) — no host recompute.  Racing makes
        # the per-cycle fan size variable; report the range actually
        # used, not cycle 0's.
        fmin = min(st["min_fan"] for st in conf.values())
        fmax = max(st["max_fan"] for st in conf.values())
        f_txt = (f"F={fmin:.0f}" if fmin == fmax
                 else f"F={fmin:.0f}..{fmax:.0f}")
        parts = " ".join(
            f"{n}=±{st['mean_ci']:.2f}(w{st['mean_width']:.1f})"
            for n, st in sorted(conf.items()))
        print(f"fan confidence ({f_txt}, mean 95% CI half-width, "
              f"member spread): {parts}")
    if race is not None and twin.telemetry.cycles:
        cs = [c for c in twin.telemetry.cycles if c.race_stopped]
        if cs:
            memb = sum(c.race_members for c in cs)
            full = len(cs) * race.f_max * len(pool)
            stops = {}
            for c in cs:
                stops[c.race_stopped] = stops.get(c.race_stopped, 0) + 1
            print(f"race: {memb} members over {len(cs)} cycles vs "
                  f"{full} fixed-F ({full / max(memb, 1):.1f}x "
                  f"reduction), mean {memb / len(cs):.1f}/cycle, "
                  f"stops {stops}")
    lat = twin.telemetry.cycle_latency_stats()
    print(f"cycle latency (decide + its one read): p50 "
          f"{lat['p50_s'] * 1e3:.2f} ms, p95 {lat['p95_s'] * 1e3:.2f} ms, "
          f"mean {lat['mean_s'] * 1e3:.2f} ms, max "
          f"{lat['max_s'] * 1e3:.2f} ms over {lat['n']} cycles")
    print("cycle stages, p50 ms: " + ", ".join(
        f"{name.split('.')[-1]} {s * 1e3:.3f}"
        for name, s in lat["stage_p50_s"].items())
        + f"; host reads {lat['host_reads']:.1f}, uploads "
        f"{lat['uploads']:.1f}, upload_buffers {lat['upload_buffers']:.1f}, "
        f"events {lat['events']:.2f}, live_jobs "
        f"{lat['live_jobs']:.1f}, recycled {lat['recycled']:.2f} per "
        f"cycle; window_full {lat['window_full']}; attach "
        f"{lat['attach_s'] * 1e3:.3f} ms")
    res = twin.telemetry.resilience_stats()
    print(f"resilience: miss_rate={res['miss_rate']:.3f} "
          f"(misses={res['deadline_misses']}/{res['cycles']}, "
          f"ladder_engaged={res['ladder_engaged']}, "
          f"max_level={res['max_level']}), ingest: "
          f"quarantined={res['quarantined']} dup={res['duplicates']} "
          f"reordered={res['reordered']} gaps={res['gaps']} "
          f"lost={res['lost']} resyncs={res['resyncs']} "
          f"read_retries={res['read_retries']}")
    health = bus.health()
    print(f"bus health: {health}"
          + (f", chaos injected: {view.stats}" if args.chaos else ""))
    if twin.dead_letters:
        print(f"dead letters: {len(twin.dead_letters)} quarantined "
              f"(first: {twin.dead_letters[0].reason})")
    # Quarantine keeps the twin serving, but outside --chaos (where
    # faults are injected on purpose) a quarantined event or a failed
    # bus subscriber is a fault of the run: exit nonzero.
    if not args.chaos and (twin.dead_letters or health["callback_failures"]):
        raise SystemExit(
            f"twin_loop: {len(twin.dead_letters)} dead letter(s), "
            f"{health['callback_failures']} bus callback failure(s)")


if __name__ == "__main__":
    main()
