"""The comparison that decides ``correct`` fails what it must.

* The control: the reference computed in bfloat16, put in the
  program's place, fails a number of the cell.
* A run whose timed path is broken underneath reads ``correct`` false,
  once for each fault a cell can have: a step that leaves its state
  unchanged, half of the batch left out, an answer altered where it is
  produced, and (fleet, in its own process on four virtual devices)
  the exchange between chips left out.
* The same runs with nothing broken read ``correct`` true.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import reference as ref, spec
from bench.record import Record
from bench.tests.helpers import run_tiny, tiny_cell


@pytest.fixture(autouse=True)
def no_disk_cache(monkeypatch):
    import repro.launch.cache
    monkeypatch.setattr(repro.launch.cache, "enable_persistent_cache",
                        lambda enabled=True: None)


def _over(result):
    return [k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", ["paper32.twin", "paper32.grid"])
def test_sound_run_is_correct(workload):
    result = run_tiny(tiny_cell(workload))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("workload", ["paper32.twin", "paper32.grid"])
def test_control_fails(workload):
    cell = tiny_cell(workload)
    driver = cell.driver(cell, seed=5)
    driver.warm()
    record = Record()
    for _ in range(2):
        driver.step(record, traced=False)
    driver.memory()
    program = driver.judge(5)
    control = driver.judge(5, control=ref.BF16)
    limits = cell.traffic["limits"]
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control


def _altered_decide(orig):
    """Decisions that start one job fewer whenever they start several
    (the job left out starts at a later cycle)."""
    def decide(self, state, pool, objective=None, **kw):
        d = orig(self, state, pool, objective, **kw)
        mask = np.asarray(d.run_mask)
        if mask.sum() > 1:
            mask = mask.copy()
            mask[np.flatnonzero(mask)[-1]] = False
        return d._replace(run_mask=jax.numpy.asarray(mask))
    return decide


def _half_mean(orig):
    """Metrics whose means run over the first half of the jobs only."""
    def metrics(state, eval_mask, runtime=None):
        keep = jax.numpy.cumsum(eval_mask) <= (eval_mask.sum() + 1) // 2
        return orig(state, eval_mask & keep, runtime)
    return metrics


def _half_grid(orig):
    def replay_grid(self, scenarios, pool, objective=None, **kw):
        from repro.cluster.workload import slice_scenarios
        S = scenarios.n_scenarios
        half = slice_scenarios(scenarios, 0, S // 2)
        out = orig(self, half, pool, objective, **kw)
        twice = lambda x: jax.numpy.concatenate([x, x])     # noqa: E731
        return out._replace(start_t=twice(out.start_t),
                            end_t=twice(out.end_t),
                            metrics=jax.tree.map(twice, out.metrics),
                            deadlocked=twice(out.deadlocked),
                            costs=twice(out.costs), best=twice(out.best))
    return replay_grid


def _altered_grid(orig):
    def replay_grid(self, scenarios, pool, objective=None, **kw):
        out = orig(self, scenarios, pool, objective, **kw)
        return out._replace(start_t=out.start_t.at[:, :, 0].add(1.0))
    return replay_grid


def _unchanged(st, started):
    return st


@pytest.mark.parametrize("workload,fault", [
    ("paper32.twin", "state_unchanged"),
    ("paper32.twin", "half_batch"),
    ("paper32.twin", "answer_altered"),
    ("paper32.grid", "state_unchanged"),
    ("paper32.grid", "half_batch"),
    ("paper32.grid", "answer_altered"),
])
def test_fault_reads_not_correct(monkeypatch, workload, fault):
    import repro.core.des
    from repro.core.engine import DrainEngine
    if fault == "state_unchanged":
        monkeypatch.setattr(repro.core.des, "apply_starts", _unchanged)
    elif workload.endswith("twin") and fault == "half_batch":
        monkeypatch.setattr(repro.core.des, "state_metrics",
                            _half_mean(repro.core.des.state_metrics))
    elif workload.endswith("twin"):
        monkeypatch.setattr(DrainEngine, "decide",
                            _altered_decide(DrainEngine.decide))
    else:
        wrap = {"half_batch": _half_grid,
                "answer_altered": _altered_grid}[fault]
        monkeypatch.setattr(DrainEngine, "replay_grid",
                            wrap(DrainEngine.replay_grid))
    jax.clear_caches()
    try:
        result = run_tiny(tiny_cell(workload))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not result["correct"] and _over(result), result["checks"]


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_fleet_exchange_left_out_reads_not_correct(fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.fleet_fault", fault],
        cwd=spec.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    verdict = proc.stdout.strip().splitlines()[-1]
    assert verdict == ("correct True" if fault == "none"
                       else "correct False"), proc.stdout[-2000:]


def test_traced_run_reports_span_metrics_from_the_window():
    """``--trace 1``: the window runs untraced, then a traced stretch;
    the span metrics come from the window's cycles.  (No TPU plane on
    the CPU, so the device shares read nothing and are left out.)"""
    result = run_tiny(tiny_cell("paper32.twin"), trace=1)
    assert result["correct"], result["checks"]
    assert {"host_ms.twin", "decide_ms.twin",
            "decide_p95_ms.twin"} <= set(result["metrics"])
    assert "device_idle.twin" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
