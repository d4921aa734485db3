"""The benchmark's own tests run on the CPU, at the program's smoke
sizes: ``python -m pytest benchmarks/chip/tests``.  The CPU backend
shows four devices, so that a four-chip cell's mesh is rehearsed too."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
