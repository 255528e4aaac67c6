"""Chip benchmark of the Q-GenX system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chips of this machine and
prints one JSON result line.  Everything that belongs to one model
configuration, traffic mix, per-layer metric or cell lives in a file of
its own under this directory and is found by the name the cell gives.
"""
