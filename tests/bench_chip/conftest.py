"""Make the chip benchmark's own modules importable by the tests."""
import os
import sys

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)
