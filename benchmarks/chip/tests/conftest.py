"""Tests of the benchmark itself, at rehearsal sizes on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
