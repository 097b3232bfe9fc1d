"""On-chip serving benchmark: the yardstick for every later change.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` (a model configuration under a traffic
mix) through the program's serving engine and prints one JSON result line.
Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own under this directory, found by its name.
"""
