"""The Polaris-scale deployment (``polaris560``): its trace family
against the program's generator, the stream's shape, both cells through
the harness at a CPU size, and ``correct`` failing for the job table's
own faults."""
import copy
import dataclasses

import jax
import numpy as np
import pytest

from bench import gen, spec
from bench.tests.helpers import run_tiny

CONFIG = spec.load_cell("polaris560.grid").config


@pytest.fixture(autouse=True)
def no_disk_cache(monkeypatch):
    import repro.launch.cache
    monkeypatch.setattr(repro.launch.cache, "enable_persistent_cache",
                        lambda enabled=True: None)


@pytest.mark.parametrize("seed", [0, 560, 2**33 + 5])
def test_family_draws_the_programs_jobs(seed):
    from repro.cluster.workload import lublin_trace
    t = CONFIG["trace"]
    params = {k: t[k] for k in ("size", "runtime", "arrival")}
    prog = lublin_trace(CONFIG["n_jobs"], CONFIG["total_nodes"], seed=seed,
                        load=t["load"], accuracy=tuple(t["accuracy"]),
                        max_walltime=t["max_walltime"], params=params)
    mine = gen.draw(spec.family("lublin"), CONFIG, seed)
    np.testing.assert_array_equal(mine.submit_t, [j.submit_t for j in prog])
    np.testing.assert_array_equal(mine.nodes, [j.nodes for j in prog])
    np.testing.assert_array_equal(mine.est_runtime,
                                  [j.est_runtime for j in prog])
    np.testing.assert_array_equal(mine.true_runtime,
                                  [j.true_runtime for j in prog])


def test_stream_has_the_models_shape():
    """The configuration's own stream: serial and power-of-two shares
    near the model's (0.2927; 0.7866 of the parallel jobs, serial jobs
    being 2**0 too), sizes within the machine, runtime <= estimate <=
    24 h, arrivals in order, and the offered load as configured."""
    t = CONFIG["trace"]
    for tr in gen.scenario_traces(spec.family("lublin"), CONFIG, 3, 7):
        n = len(tr)
        assert n == CONFIG["n_jobs"]
        serial = np.mean(tr.nodes == 1)
        pow2 = np.mean((tr.nodes & (tr.nodes - 1)) == 0)
        assert abs(serial - t["size"]["p_serial"]) < 0.04
        want = t["size"]["p_serial"] + (1 - t["size"]["p_serial"]) * (
            t["size"]["p_pow2"])
        assert pow2 >= want - 0.04
        assert tr.nodes.min() >= 1
        assert tr.nodes.max() <= CONFIG["total_nodes"]
        assert np.all(tr.true_runtime >= 1.0)
        assert np.all(tr.true_runtime <= tr.est_runtime)
        assert np.all(tr.est_runtime <= t["max_walltime"])
        assert np.all(np.diff(tr.submit_t) >= 0) and tr.submit_t[0] == 0.0
        load = (tr.nodes * tr.true_runtime).sum() / (
            CONFIG["total_nodes"] * tr.submit_t[-1])
        assert load == pytest.approx(t["load"])


def tiny_polaris(workload: str, **traffic) -> spec.Cell:
    """``workload``'s cell on a 64-node cut of the stream, the reference
    backend, and the traffic overrides given."""
    full = spec.load_cell(workload)
    config = copy.deepcopy(full.config)
    mix = copy.deepcopy(full.traffic)
    mix["backend"] = "reference"
    if mix["driver"] == "grid":
        config.update(total_nodes=64, n_jobs=48, max_jobs=64)
        mix.update(pool="extended")
    else:
        config.update(total_nodes=64, n_jobs=160, max_jobs=160)
        mix.update(traces=2, attach_range=[80, 120], window=48, cycles=8,
                   check_cycles=6)
    mix.update(traffic)
    return dataclasses.replace(full, config=config, traffic=mix)


@pytest.mark.parametrize("workload", ["polaris560.twin", "polaris560.grid"])
def test_cell_runs_through_the_harness(workload):
    result = run_tiny(tiny_polaris(workload), trace=1)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["window_s"] > 0
    if workload.endswith("twin"):
        assert {"live_jobs.twin", "slots_ms.twin", "decide_ms.twin",
                "host_ms.twin"} <= set(result["metrics"])
        assert 0 < result["metrics"]["live_jobs.twin"]["value"] <= 48
    else:
        assert "lockstep_iters.grid" in result["metrics"]


def test_twin_cell_reports_its_end_to_end_metrics():
    result = run_tiny(tiny_polaris("polaris560.twin"), trace=0)
    assert result["correct"], result["checks"]
    assert {"setup_s", "cycle_p50_ms", "cycle_p95_ms"} <= set(
        result["metrics"])


def _slots_recorded(orig):
    """Cycles that record the slots of the jobs they start in place of
    their ids (the jobs qrun starts are the right ones)."""
    def record(self, *args):
        job_ids = orig(self, *args)
        self.telemetry.cycles[-1].started_jobs = [
            self.slots.slot(j) for j in job_ids]
        return job_ids
    return record


@pytest.mark.parametrize("fault", ["none", "slot_not_cleared",
                                   "slots_for_ids"])
def test_job_table_fault_reads_not_correct(monkeypatch, fault):
    from repro.core import sync
    from repro.core.twin import SchedTwin
    if fault == "slot_not_cleared":
        monkeypatch.setattr(sync, "release", lambda state, slot: state)
    elif fault == "slots_for_ids":
        monkeypatch.setattr(SchedTwin, "_record_cycle",
                            _slots_recorded(SchedTwin._record_cycle))
    jax.clear_caches()
    try:
        result = run_tiny(tiny_polaris("polaris560.twin"))
    finally:
        monkeypatch.undo()
    assert result["correct"] == (fault == "none"), result["checks"]
