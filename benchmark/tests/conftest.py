"""Run by hand, on the CPU:  JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
(not collected by the repo's tier-1 run, which is given tests/)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
