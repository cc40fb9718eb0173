"""The benchmark's own tests: `python -m pytest benchmark/tests`, on the
CPU. Not collected by the repo's tier-1 run (`tests/`)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
