"""Tests of the benchmark itself, on the CPU and at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
