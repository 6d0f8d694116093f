"""On-chip benchmark of the scheduling twin: one cell per run.

Entry point: ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.  Cells, configurations, traffic mixes
and metrics are found by name from ``BENCHMARK.json`` and the files
under this directory; see ``bench/run.py``.
"""
