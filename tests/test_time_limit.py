"""The per-test time limit of ``tests/conftest.py``: a test that outlives
its ``@pytest.mark.time_limit`` fails with the limit named, and the run
goes on to its end instead of hanging."""
from __future__ import annotations

from pathlib import Path

import pytest

CONFTEST = Path(__file__).with_name("conftest.py")


def test_time_limit_fails_only_the_test_that_outlives_it(pytester):
    pytester.makeconftest(CONFTEST.read_text())
    pytester.makeini("[pytest]\nmarkers =\n    time_limit(seconds): limit\n")
    pytester.makepyfile(test_inner="""
        import time

        import pytest

        @pytest.mark.time_limit(1)
        def test_sleeps():
            time.sleep(5)

        def test_after():
            pass
    """)
    # a subprocess: the inner run must not disarm this test's own alarm
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(failed=1, passed=1)
    assert result.ret == pytest.ExitCode.TESTS_FAILED
    result.stdout.fnmatch_lines([
        "*TimeLimitExceeded: *test_sleeps exceeded its time limit of 1 s",
        "FAILED test_inner.py::test_sleeps*"])
