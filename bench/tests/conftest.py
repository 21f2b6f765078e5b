"""CPU set-up for the benchmark's own tests.

Same placeholder device count as ``tests/conftest.py`` (the four-chip
cell runs here on four of them).  Nothing of ``repro`` is imported while
test modules load: the kernels read their mode from the environment on
import, and the suite in ``tests/`` sets it.
"""

import os
import sys
from pathlib import Path

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)
