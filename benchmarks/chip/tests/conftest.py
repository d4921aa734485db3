"""The benchmark's own tests run on the CPU, at the program's smoke
sizes: ``python -m pytest benchmarks/chip/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
