"""`python -m pytest benchmark/checks -q` — the yardstick's own checks,
run by hand on the CPU in a few seconds; not part of tests/."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
