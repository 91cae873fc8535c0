"""The per-test time limit of tests/conftest.py: a test that overruns its
limit fails with every thread's stack printed, and the run goes on."""

import os
from pathlib import Path

import pytest

CONFTEST = Path(__file__).with_name("conftest.py")
SRC = Path(__file__).resolve().parents[1] / "src"


# under xdist the stacks must reach the report, not kill the worker
@pytest.mark.parametrize("workers", [[], ["-p", "xdist", "-n", "1"]],
                         ids=["serial", "xdist"])
def test_overrun_fails_with_stack_and_run_goes_on(pytester, monkeypatch,
                                                  workers):
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        p for p in (str(SRC), os.environ.get("PYTHONPATH")) if p))
    pytester.makeconftest(CONFTEST.read_text())
    pytester.makeini("[pytest]\nmarkers =\n    timeout(seconds): limit\n")
    pytester.makepyfile(test_inner="""
        import time
        import pytest

        @pytest.mark.timeout(0.5)
        def test_sleeps_past_its_limit():
            time.sleep(30)

        def test_after_the_overrun():
            assert True
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider",
                                           *workers, timeout=120)
    result.assert_outcomes(failed=1, passed=1)
    out = result.stdout.str()
    assert "Timeout: over its 0.5 s limit" in out
    # faulthandler's dump names the frame the test was stuck in
    assert "most recent call first" in out
    assert "test_sleeps_past_its_limit" in out
    assert result.duration < 60
