"""Tests of the chip benchmark (chip_bench/): the repository root on the
path, so `chip_bench` imports as a package."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
