"""The benchmark's tests run under the repository's per-test time limit:
the hooks of ``tests/conftest.py``, loaded from there, reach this directory
too (a conftest's hooks reach only the tests below it)."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_tests_time_limit",
    Path(__file__).resolve().parents[1] / "tests" / "conftest.py")
_limit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_limit)

pytest_runtest_setup = _limit.pytest_runtest_setup
pytest_runtest_call = _limit.pytest_runtest_call
pytest_runtest_teardown = _limit.pytest_runtest_teardown
