"""The harness's own tests: ``python -m pytest benchmark/tests -q``. None
needs a chip, and only test_new_block.py compiles a model (a toy, on the
CPU); the end-to-end rehearsal on the CPU is a command
(benchmark/README.md), not a test."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """What a run writes (.bench_run/) goes to the test's own directory."""
    from benchmark.lib import manifest
    monkeypatch.setattr(manifest, "RUN_DIR", str(tmp_path))
    return str(tmp_path)
