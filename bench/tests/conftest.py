"""The benchmark's own tests run on the CPU, at toy sizes where they serve
at all; the harness's modules and the program are put on the path here."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
