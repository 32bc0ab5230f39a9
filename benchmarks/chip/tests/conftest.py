"""The benchmark's own tests: run them with ``pytest benchmarks/chip/tests``.

They run on the CPU at tiny sizes and import the benchmark as ``run.py``
does, with ``benchmarks/chip`` and the program's ``src`` on the path.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
