"""The benchmark's own tests: CPU only, tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

(from the root of the repository; the repository's tier-1 suite lives
in ``tests/`` and does not collect these)."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
