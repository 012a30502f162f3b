"""CPU tests of the benchmark's own yardstick: `pytest benchmarks/tests -q`
(not part of the repo's tier-1 suite)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
