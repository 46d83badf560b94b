"""Chip benchmark of the serving engine, driven by `BENCHMARK.json`.

`python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell once on the TPU it is started on and prints
one JSON result line.  Configurations, traffic mixes, per-layer metric
readers and per-cell correctness limits are files of their own, found by
the names `BENCHMARK.json` gives.  Nothing here is imported by the
program; the reference model imports nothing of the program.
"""
