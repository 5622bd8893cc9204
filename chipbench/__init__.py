"""chipbench — the benchmark of cocoa-tpu on the chip (BENCHMARK.json).

Everything that belongs to one configuration, one job (traffic mix), one
per-layer metric, one generator or one job check is a file of its own,
found by the name BENCHMARK.json gives it (``registry.py``).  The yardstick
— data generation, the plain reference, the trace reducer, the peaks, the
FLOP/byte model — lives here, not in the program it measures.
"""
