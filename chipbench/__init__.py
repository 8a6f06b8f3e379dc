"""Chip benchmark of the paged serve engine: one command per cell,
``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``, with cells, configurations, traffic mixes and metric
readers found by name from ``BENCHMARK.json``."""
