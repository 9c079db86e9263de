"""The benchmark's tests import its harness (`bench/`) and the program
(`src/`) by path, as `bench/run.py` does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for path in (BENCH, BENCH / "tests", BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
