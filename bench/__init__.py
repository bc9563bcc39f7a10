"""Chip benchmark of the served STHC video search and the hybrid 3-D CNN.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.
"""
