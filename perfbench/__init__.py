"""The benchmark of this repository: everything ``BENCHMARK.json`` names.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once on the TPU it is started on and prints one JSON line.
``README.md`` beside this file says how a later PR adds a configuration, a
traffic mix, a driver kind, a per-layer metric or a cell as new files.
"""
