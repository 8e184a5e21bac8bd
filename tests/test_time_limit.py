"""The per-test time limit in tests/conftest.py fails a test that runs
past its ``timeout`` marker, with the limit's message and the stack of
where it was. Run in a child pytest so the failure stays there."""

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_timeout_marker_fails_a_test_that_overruns(pytester, monkeypatch):
    monkeypatch.setenv("PYTHONPATH", ROOT)
    with open(os.path.join(ROOT, "tests", "conftest.py")) as f:
        pytester.makeconftest(f.read())
    pytester.makepyfile(test_overrun="""
        import time

        import pytest


        @pytest.mark.timeout(1)
        def test_sleeps_past_its_limit():
            time.sleep(30)


        @pytest.mark.timeout(5)
        def test_inside_its_limit():
            time.sleep(0.1)
        """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider",
                                           "-p", "no:xdist")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines([
        "*test_sleeps_past_its_limit exceeded its 1 s time limit*"])
    result.stdout.fnmatch_lines(["*time.sleep(30)*"])
    assert result.duration < 25
