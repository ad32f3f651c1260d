"""The benchmark's own checks: `python -m pytest benchmarks/selfcheck -q`
on the CPU.  Not part of `tests/`, so the tier-1 count is untouched."""

import os
import sys

# run from anywhere: the checkout's root holds `benchmarks` and the engine
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
