"""Chip benchmark: time to solution of task programs on one TPU chip.

``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything that
measures (traffic generation, the NumPy reference, the least-bytes
arithmetic, the peak table, the trace reduction) lives in this directory;
from the program it takes only the system under test.
"""
