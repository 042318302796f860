"""Chip benchmark of the SLA serving paths (see PERF.md and BENCHMARK.json).

`bench/run.py` runs one cell once. Everything that belongs to one model
configuration, traffic mix, cell or per-layer metric is a file of its own
under this directory, found by the name `BENCHMARK.json` gives it.
"""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the system under test lives in <checkout>/src
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
