"""Chip benchmark of the SFPL round: ``python3 benchmarks/chip/run.py
--workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it (``spec.py``)."""
