"""`python -m pytest perfbench/tests`: the benchmark's own tests, outside
tier-1. They run on the CPU and never look for a chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
