"""The benchmark: one cell, one run, one JSON line (``benchmarks/run.py``).

Everything a later PR may not change lives here: traffic generation, the
metric arithmetic, the trace reduction, the peaks, the kernels' operation
and byte counts, the plain references and the comparison behind
``correct``. From the program it takes the system under test and its
counters, timers and kernel names.
"""
