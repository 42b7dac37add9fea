"""CPU tests of the benchmark's own code (manifest, work counts, trace
reduction, the run's refusals, its comparison, and a cell added by files
alone)."""
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
