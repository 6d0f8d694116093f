"""Scheduling-cycle overhead — the paper's "a few seconds per cycle".

Measures (a) the twin's per-cycle decision latency during a live run
(the paper's metric), (b) the steady-state latency of the jitted
what-if engine alone (post-compilation — what a persistent daemon
pays), (c) a backend shoot-out across policy pool sizes: the
policy-batched ``DrainEngine`` (``reference`` and ``pallas`` backends)
against the legacy ``jax.vmap``-over-scalar-DES path it replaced
(DESIGN.md §3), (d) **parametric sweep pools**: θ-grid
``PolicySpec`` pools at k∈{16, 64, 128} plus the DRAS-style 25-point
(WFP exponent × aging timescale) sweep riding with the 7 static specs
(k=32, ``configs.schedtwin.DRAS_SWEEP_POOL``) — the per-cycle latency
the tentpole's parameter-sweep drains cost — and (e) the **hot-loop
compaction ablation** (DESIGN.md §7): decide latency and drain
pass-invocation counts under each compaction knob.  Everything is
emitted as a ``BENCH_overhead.json`` artifact.

CLI:
    PYTHONPATH=src python benchmarks/overhead.py               # {3,7,32}
    PYTHONPATH=src python benchmarks/overhead.py --pool 7      # one size
    PYTHONPATH=src python benchmarks/overhead.py --smoke       # CI: 1 rep
    PYTHONPATH=src python benchmarks/overhead.py --out bench.json
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.workload import paper_synthetic_trace
from repro.configs.schedtwin import DRAS_SWEEP_POOL
from repro.core import whatif
from repro.core.engine import DrainEngine
from repro.core.policies import (EXTENDED_POOL, PAPER_POOL, PolicyPool,
                                 parse_pool, wfp_spec)

POOL_SIZES = (3, 7, 32)
SWEEP_SIZES = (16, 64, 128)


def _bench(fn, n_iter: int = 20, repeats: int = 3) -> float:
    """Mean seconds/call over ``n_iter`` calls after a warm-up, best of
    ``repeats`` (rejects scheduler noise on shared CPU runners)."""
    fn()  # warm-up / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n_iter):
            fn()
        best = min(best, (time.perf_counter() - t0) / n_iter)
    return best


def make_pool(k: int) -> jax.Array:
    """A k-policy legacy id pool: the 7 distinct policies cycled to
    length k (positions past the first occurrence only matter for
    tie-breaks)."""
    ids = [EXTENDED_POOL[i % len(EXTENDED_POOL)] for i in range(k)]
    return jnp.asarray(ids, dtype=jnp.int32)


def make_sweep_pool(k: int) -> PolicyPool:
    """A k-fork parametric pool: the 7 statics + a (k-7)-point θ-grid
    over the WFP exponent — every fork is a distinct point in policy
    space (unlike ``make_pool``'s cycled ids)."""
    statics = parse_pool("extended")
    n = k - len(statics)
    if n <= 0:
        raise ValueError(f"sweep pool needs k > {len(statics)}, got {k}")
    grid_a = np.linspace(0.5, 5.0, n)
    grid = PolicyPool.from_specs(
        [wfp_spec(a=float(a)) for a in grid_a],
        names=[f"wfp[a={a:g}]" for a in grid_a])
    return statics + grid


def bench_engines(state, pool_sizes: Sequence[int] = POOL_SIZES,
                  n_iter: int = 20, repeats: int = 3
                  ) -> Dict[str, Dict[str, float]]:
    """Per-pool-size cycle latency: legacy vmap vs batched engine."""
    ref = DrainEngine("reference")
    pal = DrainEngine("pallas")   # interpret auto: CPU here, compiled on TPU
    out: Dict[str, Dict[str, float]] = {}
    for k in pool_sizes:
        pool = make_pool(k)
        timers = {
            "legacy_vmap_us": lambda: whatif.decide_legacy_vmap(state, pool),
            "engine_reference_us": lambda: ref.decide(state, pool),
            "engine_pallas_us": lambda: pal.decide(state, pool),
        }
        row: Dict[str, float] = {}
        for name, thunk in timers.items():
            row[name] = _bench(
                lambda t=thunk: jax.block_until_ready(t().costs),
                n_iter, repeats) * 1e6
        row["speedup_ref_vs_legacy"] = (
            row["legacy_vmap_us"] / max(row["engine_reference_us"], 1e-9))
        out[str(k)] = row
    return out


def bench_sweep_pools(state, sweep_sizes: Sequence[int] = SWEEP_SIZES,
                      n_iter: int = 5, repeats: int = 2
                      ) -> Dict[str, Dict[str, float]]:
    """θ-sweep PolicySpec pools through the reference engine (the
    pallas-vs-reference trade is already measured by ``bench_engines``;
    sweep latency scales with k the same way since θ lives in stage 1,
    outside the pass backend)."""
    ref = DrainEngine("reference")
    out: Dict[str, Dict[str, float]] = {}
    for k in sweep_sizes:
        pool = make_sweep_pool(k)
        us = _bench(
            lambda p=pool.spec: jax.block_until_ready(
                ref.decide(state, p).costs),
            n_iter, repeats) * 1e6
        out[str(k)] = {"engine_reference_us": us, "k": float(k)}
    return out


def bench_compaction(state, n_iter: int = 10, repeats: int = 2
                     ) -> Dict[str, Dict[str, float]]:
    """Hot-loop compaction ablation on the decide path (DESIGN.md §7):
    per-cycle latency of the k=7 extended pool under every compaction
    knob combination, plus the drain's pass-invocation count and the
    pool's static/time-varying fork split — so BENCH_overhead.json
    records which optimization is paying on the what-if (drain) side,
    mirroring BENCH_replay.json's replay-side ablation."""
    from repro.core.policies import time_invariant_mask
    pool = make_pool(7)
    combos = {
        "full": {},
        "no_dynamic_bounds": dict(dynamic_bounds=False),
        "no_hoist": dict(hoist_static=False),
        "pr3_equivalent": dict(dynamic_bounds=False, hoist_static=False,
                               elide_empty=False),
    }
    out: Dict[str, Dict[str, float]] = {}
    for name, knobs in combos.items():
        eng = DrainEngine("reference", **knobs)
        us = _bench(
            lambda: jax.block_until_ready(eng.decide(state, pool).costs),
            n_iter, repeats) * 1e6
        res = eng.drain(state, pool)
        out[name] = {
            "engine_reference_us": us,
            "pass_invocations": float(np.asarray(res.pass_invocations)[0]),
        }
    ti = time_invariant_mask(pool)
    out["full"]["forks_static"] = float(ti.sum())
    out["full"]["forks_time_varying"] = float((~ti).sum())
    pr3 = out["pr3_equivalent"]["engine_reference_us"]
    for row in out.values():
        row["speedup_vs_pr3"] = pr3 / max(row["engine_reference_us"], 1e-9)
    return out


def bench_dras_sweep(state, n_iter: int = 5, repeats: int = 2
                     ) -> Dict[str, float | str]:
    """The acceptance sweep: DRAS-style 5x5 grid over the WFP exponent
    and aging timescale + the 7 statics (k=32) in ONE batched drain —
    the same pool ``twin_loop --pool "<DRAS_SWEEP_POOL>"`` runs live."""
    pool = parse_pool(DRAS_SWEEP_POOL)
    ref = DrainEngine("reference")
    us = _bench(
        lambda: jax.block_until_ready(ref.decide(state, pool.spec).costs),
        n_iter, repeats) * 1e6
    return {
        "grammar": DRAS_SWEEP_POOL,
        "k": float(len(pool)),
        "engine_reference_us": us,
    }


def write_artifact(engines: Dict[str, Dict[str, float]], path: str,
                   extra: Optional[Dict] = None) -> None:
    doc = {
        "benchmark": "overhead",
        "backend": jax.default_backend(),
        "pools": engines,
    }
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def main(seed: int = 0, pool_sizes: Sequence[int] = POOL_SIZES,
         out: str = "BENCH_overhead.json", live: bool = True,
         smoke: bool = False) -> List[str]:
    lines = []
    extra: Dict = {}
    n_iter, repeats = (1, 1) if smoke else (20, 3)
    n_iter_sweep, repeats_sweep = (1, 1) if smoke else (5, 2)

    if live:
        # (a) live per-cycle latency (includes first-call compilation)
        from benchmarks.figure3_radar import run_all
        _, twin = run_all(seed=seed)
        stats = twin.telemetry.cycle_latency_stats()
        lines.append(
            f"overhead,live_cycle,mean_s={stats['mean_s']:.4f},"
            f"p50_s={stats['p50_s']:.4f},p95_s={stats['p95_s']:.4f},"
            f"max_s={stats['max_s']:.4f},n={stats['n']},"
            f"host_reads={stats['host_reads']:.1f},"
            f"uploads={stats['uploads']:.1f},"
            f"events={stats['events']:.2f},paper=a few seconds")
        extra["live_cycle"] = stats

    state = snapshot_state(seed)

    # (b) steady-state decision latency, k=3 paper pool, batched engine
    pool3 = jnp.asarray(PAPER_POOL, dtype=jnp.int32)
    eng = DrainEngine("reference")
    t3 = _bench(lambda: jax.block_until_ready(eng.decide(state, pool3).costs),
                n_iter, repeats)
    lines.append(f"overhead,steady_cycle_k3,us_per_call={t3 * 1e6:.0f}")

    # (c) backend shoot-out across pool sizes -> JSON artifact
    engines = bench_engines(state, pool_sizes, n_iter, repeats)
    for k, row in engines.items():
        lines.append(
            f"overhead,engines_k{k},"
            + ",".join(f"{n}={v:.0f}" for n, v in sorted(row.items())
                       if n.endswith("_us"))
            + f",speedup_ref_vs_legacy={row['speedup_ref_vs_legacy']:.2f}x")

    # (d) parametric θ-sweep pools (tentpole): k in {16, 64, 128} + the
    # DRAS-style k=32 acceptance sweep
    sweeps = bench_sweep_pools(state, SWEEP_SIZES, n_iter_sweep,
                               repeats_sweep)
    for k, row in sweeps.items():
        lines.append(f"overhead,sweep_k{k},"
                     f"engine_reference_us={row['engine_reference_us']:.0f}")
    extra["sweep_pools"] = sweeps
    dras = bench_dras_sweep(state, n_iter_sweep, repeats_sweep)
    lines.append(
        f"overhead,dras_sweep,k={dras['k']:.0f},"
        f"engine_reference_us={dras['engine_reference_us']:.0f},"
        f"grammar={dras['grammar']}")
    extra["dras_sweep"] = dras

    # (d2) hot-loop compaction ablation on the decide path (§7)
    compaction = bench_compaction(state, n_iter_sweep, repeats_sweep)
    for name, row in compaction.items():
        lines.append(
            f"overhead,compaction_{name},"
            f"engine_reference_us={row['engine_reference_us']:.0f},"
            f"passes={row['pass_invocations']:.0f},"
            f"speedup_vs_pr3={row['speedup_vs_pr3']:.2f}x")
    extra["compaction"] = compaction

    write_artifact(engines, out, extra)
    lines.append(f"overhead,artifact,path={out}")

    # (e) the kernelized scheduling pass alone (shared-snapshot variant)
    from repro.kernels import ops
    pool7 = jnp.asarray(EXTENDED_POOL, dtype=jnp.int32)
    tk = _bench(
        lambda: jax.block_until_ready(ops.twin_schedule_pass(state, pool7)[0]),
        n_iter, repeats)
    lines.append(f"overhead,kernel_pass_k7,us_per_call={tk * 1e6:.0f}")
    return lines


# -- helper: a mid-trace snapshot with a busy queue --------------------

def snapshot_state(seed: int):
    from repro.core.state import add_job, empty_state, start_job
    trace = paper_synthetic_trace(seed=seed)
    st = empty_state(256, 32)
    free = 32
    # phase 2 moment: some burst jobs running, many queued
    for j, spec in enumerate(trace[:80]):
        st = add_job(st, spec.job_id, spec.submit_t, spec.nodes,
                     spec.est_runtime)
        if spec.nodes <= free:
            st = start_job(st, spec.job_id, spec.submit_t + 1.0)
            free -= spec.nodes
    return st._replace(now=jnp.float32(trace[79].submit_t + 5.0))


if __name__ == "__main__":
    # direct invocation (python benchmarks/overhead.py) puts benchmarks/
    # on sys.path, not the repo root; --live imports benchmarks.*
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", type=int, default=None,
                    help="bench a single pool size (default: 3, 7, 32)")
    ap.add_argument("--out", default="BENCH_overhead.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--live", action="store_true",
                    help="also run the full live-cycle co-simulation")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: 1 repeat/iteration per timer, "
                         "numbers are noisy; combine with --live to also "
                         "run the live co-simulation")
    args = ap.parse_args()
    if args.pool is not None and args.pool < 1:
        ap.error("--pool must be >= 1")
    sizes = (args.pool,) if args.pool is not None else POOL_SIZES
    for line in main(seed=args.seed, pool_sizes=sizes, out=args.out,
                     live=args.live, smoke=args.smoke):
        print(line)
