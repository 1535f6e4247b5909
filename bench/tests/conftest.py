"""Tests of the benchmark's own parts, on the CPU at small sizes.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
  if p not in sys.path:
    sys.path.insert(0, p)
