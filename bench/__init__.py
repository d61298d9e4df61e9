"""On-chip benchmark of the GW solver and server: one cell per run.

Run from the checkout's root: ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.  Cells, configurations, traffic
mixes and per-layer metrics are found by name from ``BENCHMARK.json``.
"""
