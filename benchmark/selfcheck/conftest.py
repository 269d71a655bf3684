"""Self-checks of the yardstick: run by hand on the CPU,

    JAX_PLATFORMS=cpu python -m pytest benchmark/selfcheck -q

They are not part of ``tests/`` (the tier-1 count is untouched)."""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
