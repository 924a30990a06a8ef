"""The benchmark: BENCHMARK.json's cells, run one per process by run.py.

Everything the yardstick needs lives here (traffic, references, costs,
peaks, the trace reduction, the comparison that decides ``correct``);
from the program it takes only the system under test and its counters,
host phases and kernel names.  See README.md for how to add a cell.
"""
