"""Tests of the benchmark's own code. Run by hand:

    python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 tests, import no JAX and
need no chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
