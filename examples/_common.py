"""Shared example bootstrap: repo on sys.path, small sizes in smoke mode.

The examples run on whatever platform JAX selects: a TPU where there is one,
the CPU under ``JAX_PLATFORMS=cpu`` (what ``dev/run-examples`` sets).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("ZOO_EXAMPLE_SMOKE", "0") == "1"
