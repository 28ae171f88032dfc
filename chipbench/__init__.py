"""Chip benchmark of the count-sketch optimizer (see BENCHMARK.json).

One cell runs per process: ``python chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.  Everything that belongs to one
configuration, traffic mix, cell or per-layer metric is a file of its own
under this directory, found by the name ``BENCHMARK.json`` gives it."""
