"""The benchmark's self-tests run on the CPU: ``python -m pytest bench``."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
