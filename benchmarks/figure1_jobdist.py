"""Figure 1: job-size/runtime variability (Polaris-style distribution).

The paper motivates adaptivity with the wide spread of job sizes and
runtimes on ALCF Polaris.  We draw a 2,000-job stream for Polaris's 560
nodes from the Lublin–Feitelson rigid-job model
(``workload.lublin_trace``, its batch class at 0.9 offered load, as the
``polaris560`` deployment of the benchmark draws it), report its
heavy-tailed variability (orders of magnitude between p50 and max
runtime), and the synthetic §4.1 trace's statistics alongside.
"""
from __future__ import annotations

from typing import List

from repro.cluster.workload import (lublin_trace, paper_synthetic_trace,
                                    trace_stats)


def main(seed: int = 0) -> List[str]:
    lines = []
    polaris_like = lublin_trace(n_jobs=2000, total_nodes=560, seed=seed,
                                load=0.9)
    s = trace_stats(polaris_like)
    spread = s["runtime_max_s"] / max(s["runtime_p50_s"], 1e-9)
    lines.append(
        f"figure1_jobdist,polaris_like,n={s['n_jobs']},"
        f"nodes_p50={s['nodes_p50']:.0f},nodes_max={s['nodes_max']:.0f},"
        f"rt_p50_s={s['runtime_p50_s']:.0f},rt_max_s={s['runtime_max_s']:.0f},"
        f"rt_spread={spread:.1f}x")

    paper = trace_stats(paper_synthetic_trace(seed=seed))
    lines.append(
        f"figure1_jobdist,paper_trace,n={paper['n_jobs']},"
        f"nodes_p50={paper['nodes_p50']:.0f},nodes_max={paper['nodes_max']:.0f},"
        f"rt_p50_s={paper['runtime_p50_s']:.0f},"
        f"rt_max_s={paper['runtime_max_s']:.0f}")
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
