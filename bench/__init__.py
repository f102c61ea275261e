"""Chip benchmark of the repro system: ``python3 bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``.  See BENCHMARK.json."""
