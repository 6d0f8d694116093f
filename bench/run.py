#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
(``bench/spec.py``).  The run checks that JAX holds a TPU and as many
chips as the cell asks for, and exits nonzero with no result
otherwise: there is no CPU path.  It turns on the program's persistent
compile cache (a fixed directory in the checkout), builds the cell's
inputs from the seed, warms up every shape the window uses, then runs
the window for ``--seconds`` seconds and counts compilations inside
it.  ``--trace 1`` then records a profiler trace of one more step
after the window and reports the per-layer metrics:
spans and counters from the window, device shares from the trace.
``--trace 0`` reports the end-to-end ones.

After the window the reference (``bench/reference.py``) judges a
seeded sample of what the window produced (``bench/check.py``).  Each
number compared is printed beside its limit as the last lines of
standard error and under ``checks``, the last key of the result.  The
last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse                                        # noqa: E402
import glob                                            # noqa: E402
import json                                            # noqa: E402
import shutil                                          # noqa: E402
import sys                                             # noqa: E402
import tempfile                                        # noqa: E402
from pathlib import Path                               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import counts, spec, trace                  # noqa: E402
from bench.record import Record                         # noqa: E402

#: JAX's monitoring events that mark a compilation: every lowering of a
#: jit cache miss, and every backend compile.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_gate(chips: int) -> list:
    """The chips the cell needs, or exit nonzero: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{devs[0].platform!r}); there is no CPU path")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX has "
                         f"{len(devs)}")
    return devs


class CompileCounter:
    def __init__(self):
        self.on, self.n = False, 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(args: argparse.Namespace, gate=device_gate, cell=None) -> dict:
    """One run of the cell ``args.workload`` (or of ``cell``, a
    ``spec.Cell`` built by the caller) on the chips ``gate`` returns."""
    import jax
    cell = spec.load_cell(args.workload) if cell is None else cell
    devices = gate(cell.chips)[:cell.chips]
    from repro.launch.cache import enable_persistent_cache
    enable_persistent_cache()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)

    driver = cell.driver(cell, args.seed)
    driver.warm()
    record = Record()
    record.device_kind = devices[0].device_kind
    record.n_devices = len(devices)
    record.pass_k, record.pass_j = driver.pass_k, driver.pass_j
    record.setup_s = time.perf_counter() - T0

    counter.on = True
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        driver.step(record, traced=False)
    record.window_s = time.perf_counter() - t0
    counter.on = False
    tmp = None
    if args.trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            driver.step(record, traced=True)
        jax.profiler.stop_trace()

    memory = peak_bytes(devices)
    driver.memory()
    record.failed = driver.failures()
    if tmp is not None:
        try:
            found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
            record.trace = trace.reduce_events(
                *trace.read(found[0], driver.spans), counts.PASS_KERNEL)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    t_judge = time.perf_counter()
    numbers = driver.judge(args.seed)
    record.judge_s = time.perf_counter() - t_judge
    record.judged = {k: v for k, v in driver.totals.items()
                     if not k.endswith("gap")}
    limits = cell.traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.read(record)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": record.attempted, "failed": record.failed,
              "metrics": metrics, "device": device}
    if record.trace is not None:
        device["busy_s"] = record.trace.mean_busy_s
        device["window_s"] = record.trace.window_s
        result["breakdown"] = trace.breakdown(record.trace)
    result["checks"] = checks
    result["_report"] = report_lines(driver, record, counter.n, d)
    return result


def report_lines(driver, record, compiles: int, device) -> list:
    lines = [f"device: {device.device_kind} x{record.n_devices} "
             f"({device.platform})",
             f"compiles inside the window: {compiles}",
             f"window: {record.window_s:.3f} s, setup {record.setup_s:.3f} s",
             f"reference check: {record.judge_s:.3f} s, "
             f"{record.judged}"] + driver.report(record)
    if record.trace is not None:
        for dev, busy in sorted(record.trace.busy_s.items()):
            lines.append(f"{dev}: busy {busy:.6f} s of "
                         f"{record.trace.window_s:.6f} s, pass kernel "
                         f"{record.trace.kernel_s[dev]:.6f} s")
        if record.pass_k and sum(record.trace.kernel_s.values()) > 0:
            from bench import peaks
            _, bound = counts.least_seconds(
                counts.pass_work(record.pass_k, record.pass_j),
                peaks.peak(record.device_kind))
            lines.append(f"pass roofline bound by {bound} at (k, J) = "
                         f"({record.pass_k}, {record.pass_j})")
    return lines


def emit(result: dict) -> None:
    for line in result.pop("_report"):
        print(line, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> None:
    emit(run(parse(argv)))


if __name__ == "__main__":
    main()
