"""BENCHMARK.json against the rules the harness relies on, the lookup
of every part by name, and the device gate."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark()


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_keys(group):
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source",
                              "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}}[group]
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    for e in BENCH[group]:
        assert set(e) <= allowed, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


def test_every_cell_resolves_by_name():
    cells = spec.all_cells()
    assert set(cells) == {w["name"] for w in BENCH["workloads"]}
    for name, cell in cells.items():
        for method in ("warm", "step", "memory", "failures", "judge",
                       "report"):
            assert callable(getattr(cell.driver, method)), (name, method)
        assert all(isinstance(s, str) for s in cell.driver.spans)
        assert callable(cell.family.draw) and callable(cell.family.groups)
        assert set(cell.traffic["limits"]), name
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer, name
        assert all(callable(m.read) for m in cell.end_to_end
                   + cell.per_layer)


def test_config_files_are_distinct_and_used():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        with open(spec.ROOT / c["file"]) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"]


def test_bounds_and_four_chip_share():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A mix, a metric and a cell added as files and entries are found
    by name; no existing file changes."""
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    traffic = json.loads((tmp_path / "bench" / "traffic" /
                          "twin_paper.json").read_text())
    traffic["pool"] = "extended"
    (tmp_path / "bench" / "traffic" / "twin_extended.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "cycles.twin.py").write_text(
        "def read(record):\n    return float(record.attempted)\n")
    bench["workloads"].append({"name": "paper32.extended_twin",
                               "config": "paper32",
                               "traffic": "twin_extended", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "cycles.twin", "unit": "cycles",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "decision engine",
                               "moves": "cycle_p50_ms",
                               "workloads": ["paper32.extended_twin"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("paper32.extended_twin", root=tmp_path)
    assert cell.traffic["pool"] == "extended"
    assert [m.name for m in cell.per_layer] == ["cycles.twin"]

    class Rec:
        attempted = 7
    assert cell.per_layer[0].read(Rec()) == 7.0


#: A driver and a trace family that no cell of the benchmark uses: a
#: later change adds such files, and the harness finds them by name.
COUNTING_DRIVER = """
from bench.gen import make_trace


class Driver:
    pass_k = pass_j = 0
    spans = ()

    def __init__(self, cell, seed):
        self.cell, self.seed, self.jobs = cell, seed, []

    def warm(self):
        pass

    def step(self, record, traced):
        trace = make_trace(self.cell.family, self.cell.config,
                           self.seed + len(self.jobs))
        self.jobs.append(len(trace))
        record.add("jobs", float(len(trace)))
        record.attempted += 1

    def memory(self):
        pass

    def failures(self):
        return 0

    def judge(self, seed, control=None):
        self.totals = {"steps": len(self.jobs)}
        want = self.cell.config["n_jobs"]
        return {"jobs_off": float(sum(n != want for n in self.jobs))}

    def report(self, record):
        return [f"steps: {len(self.jobs)}"]
"""

FIXED_FAMILY = """
from bench.gen import as_trace


def draw(rng, config):
    n = config["n_jobs"]
    gaps = rng.uniform(1.0, 2.0, size=n)
    return as_trace(gaps.cumsum(), [1] * n, [10.0] * n, [5.0] * n)


def groups(config):
    return [config["n_jobs"]]
"""


def test_a_new_driver_and_trace_family_need_only_new_files(tmp_path):
    """A driver, a trace family, a configuration, a mix and a metric
    added as files and entries are found by name, and a run of the new
    cell goes through the harness end to end."""
    import jax
    from bench import run
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench")
    (tmp_path / "bench" / "drivers" / "counting.py").write_text(
        COUNTING_DRIVER)
    (tmp_path / "bench" / "families" / "fixed.py").write_text(FIXED_FAMILY)
    (tmp_path / "bench" / "configs" / "fixed9.json").write_text(json.dumps(
        {"total_nodes": 4, "n_jobs": 9, "max_jobs": 16, "reduced": [],
         "trace": {"family": "fixed", "draws_seed": 1}}))
    (tmp_path / "bench" / "traffic" / "counting.json").write_text(
        json.dumps({"driver": "counting", "limits": {"jobs_off": 0.0}}))
    (tmp_path / "bench" / "metrics" / "jobs_per_step.py").write_text(
        "def read(record):\n"
        "    xs = record.samples.get('jobs')\n"
        "    return sum(xs) / len(xs) if xs else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "fixed9", "source": "test",
                             "file": "bench/configs/fixed9.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fixed9.counting", "config": "fixed9",
                               "traffic": "counting", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "jobs_per_step", "unit": "jobs",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "test", "moves": "setup_s",
                               "workloads": ["fixed9.counting"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("fixed9.counting", root=tmp_path)
    assert [m.name for m in cell.per_layer] == ["jobs_per_step"]
    args = run.parse(["--workload", cell.name, "--seed", str(2**33 + 1),
                      "--seconds", "0.05", "--trace", "0"])
    result = run.run(args, gate=lambda chips: jax.devices(), cell=cell)
    assert result["correct"] and result["attempted"] > 0
    assert result["checks"] == {"jobs_off": {"value": 0.0, "limit": 0.0}}
    assert "steps: " in result["_report"][-1]
    assert [m.read(_steps(9.0)) for m in cell.per_layer] == [9.0]


def _steps(jobs):
    from bench.record import Record
    record = Record()
    record.add("jobs", jobs)
    return record


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper32.twin",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout
