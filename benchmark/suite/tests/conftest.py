"""Tests of the benchmark's own yardstick. They run on the CPU in seconds:
``JAX_PLATFORMS=cpu python -m pytest benchmark/suite/tests -q``."""

import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
for path in (ROOT, SUITE):
    if path not in sys.path:
        sys.path.insert(0, path)
