"""The yardstick a decode step's expert layers are judged by, in tier-1: the
cases of ``benchmark/tests/test_roofline_touched.py`` (PR 55: the floor under
``decode_window_roofline`` and ``moe_roofline`` counts the experts a
layer-step's live rows TOUCHED, the program's own count), run here by path.
The harness's tests are a command of their own (``python -m pytest
benchmark/tests``) that the driver's tier-1 line does not run; since PR 56 a
window's step READS what that floor counts (``experts.touched_product``), so
the floor's cases guard every later PR from here. Nothing under
``benchmark/`` is edited or copied: the module is loaded where it lies."""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "benchmark", "tests", "test_roofline_touched.py")
_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_roofline_touched", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({name: value for name, value in vars(_module).items()
                  if name.startswith("test_")})
