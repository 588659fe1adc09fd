"""The harness's own tests: ``python -m pytest benchmark/tests -q``. None
compiles a model or needs a chip; the end-to-end rehearsal on the CPU is a
command (benchmark/README.md), not a test."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
