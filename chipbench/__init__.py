"""Chip benchmark of the Scavenger store (see PERF.md and BENCHMARK.json).

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the chip it is started on.
"""
