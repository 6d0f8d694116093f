#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--control-seeds <k>]

One process, on the chip, at the cell's own size: for each seed it
builds the cell from that seed, runs a window of ``--seconds`` through
the timed path and judges what the window produced, as a run of
``bench/run.py`` does.  For the first ``--control-seeds`` seeds it then
judges the control at the same places: the reference computed in
bfloat16 (the precision below the float32 the configurations state),
put in the program's place.  One JSON line per seed, then the largest
program reading and the smallest control reading of each number: the
lower and upper readings a limit is set between.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import reference as ref, spec           # noqa: E402
from bench.record import Record                     # noqa: E402
from bench.run import device_gate                   # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device_gate(cell.chips)
    from repro.launch.cache import enable_persistent_cache
    enable_persistent_cache()
    lower, upper = {}, {}
    for i, seed in enumerate(args.seeds):
        driver = cell.driver(cell, seed)
        if i == 0:
            driver.warm()
        record = Record()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            driver.step(record, traced=False)
        driver.memory()
        line = {"seed": seed, "failed": driver.failures(),
                "program": driver.judge(seed)}
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        if i < args.control_seeds:
            line["control"] = driver.judge(seed, control=ref.BF16)
            for k, v in line["control"].items():
                upper[k] = min(upper.get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
