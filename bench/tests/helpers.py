"""Tiny cells for CPU tests: the real drivers, traffic files and
metrics, on a 24-job Poisson stream over 16 nodes."""
import copy
import dataclasses
import json

import jax

from bench import run, spec

TINY_CONFIG = {
    "total_nodes": 16, "n_jobs": 24, "max_jobs": 64,
    "trace": {"family": "poisson", "mean_gap": 20.0, "node_range": [1, 16],
              "walltime_range": [30.0, 600.0], "accuracy": [0.3, 1.0]}}


def tiny_cell(workload: str, chips: int = 1, mix_file: str = None,
              **traffic) -> spec.Cell:
    """``workload``'s cell with the tiny stream, the reference backend,
    and the traffic overrides given (small grids check every
    scenario).  With ``mix_file`` the traffic comes from that file of
    ``bench/traffic`` instead of the workload's own."""
    full = spec.load_cell(workload)
    mix = copy.deepcopy(full.traffic)
    if mix_file is not None:
        mix = json.loads((spec.ROOT / "bench" / "traffic" /
                          f"{mix_file}.json").read_text())
    mix["backend"] = "reference"
    if mix["driver"] != "twin":
        mix.update(scenarios=4, pool="extended", check_scenarios=4,
                   block_scenarios=4)
    mix.update(traffic)
    return dataclasses.replace(
        full, chips=chips, config=copy.deepcopy(TINY_CONFIG), traffic=mix,
        driver=spec.driver(mix["driver"]),
        family=spec.family(TINY_CONFIG["trace"]["family"]))


def run_tiny(cell: spec.Cell, seed: int = 2**31 + 17,
             seconds: float = 0.5, trace: int = 0) -> dict:
    """One run of ``cell`` on whatever devices JAX has: the chip gate is
    skipped, the rest of the run is the benchmark's own."""
    args = run.parse(["--workload", cell.name, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    result = run.run(args, gate=lambda chips: jax.devices(), cell=cell)
    result.pop("_report")
    json.dumps(result)
    return result
