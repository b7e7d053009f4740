"""The harness's own checks run on the CPU: ``pytest bench/tests``."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
