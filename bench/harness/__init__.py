"""Shared pieces of the benchmark harness (``bench/run.py``).

Everything that decides a number lives under ``bench/``: the cell specs and
their loading (``spec``), the chip check and peak table (``device``), the
FLOP and byte counts (``flops``), the profiler-trace reduction (``xtrace``),
seeded weights (``weights``) and one run with its compared numbers
(``runner``). The program under test (``src/repro``) is imported only by
the traffic generators.
"""
