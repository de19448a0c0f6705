"""The benchmark's own tests: make ``pb`` importable."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
if PB not in sys.path:
    sys.path.insert(0, PB)
