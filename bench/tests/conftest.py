"""The benchmark's own tests: ``python -m pytest bench/tests`` from the root
of a checkout, on the CPU (``JAX_PLATFORMS=cpu``).  They are not part of the
repository's ``tests`` suite."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
