"""Tests of the benchmark itself.  They live here, not under ``tests/``, so
tier-1's count is what it was: run them with
``JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python -m pytest benchmarks/tests -q``.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
