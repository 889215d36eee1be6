"""On-chip serving benchmark: one cell (a model configuration under a
traffic mix) per process, driven by the files that ``BENCHMARK.json``
names.  ``python3 bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` runs one cell and prints one JSON line last."""
