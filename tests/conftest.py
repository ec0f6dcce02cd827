"""Shared test configuration.

Registers a fixed hypothesis profile so property tests are reproducible
in CI: ``derandomize=True`` makes every run draw the same example
sequence (a red nightly reproduces locally with no shrinking lottery),
and the per-example deadline is bounded but generous — first examples
pay JAX compiles; tests that interleave many compiles opt out with
``deadline=None`` in their own ``@settings``. Select another profile
with ``HYPOTHESIS_PROFILE=<name>`` (e.g. ``dev`` to re-randomize
locally).

Gives every test's call phase a time limit of its own (standard library
only): ``SIGALRM`` fails a test that outlives it, and ``faulthandler``
dumps every thread's stack to the real stderr ``DUMP_GRACE_S`` later if
the test has still not returned, so a hang inside a C call still leaves
a stack. A hang then costs one failed test, not the whole run. The
default is ``DEFAULT_TIME_LIMIT_S``; ``@pytest.mark.time_limit(seconds)``
sets another for one test.
"""
from __future__ import annotations

import faulthandler
import os
import signal
import sys

import pytest

pytest_plugins = ("pytester",)

try:
    from datetime import timedelta

    from hypothesis import settings
except ImportError:                     # fast lane runs without hypothesis
    pass
else:
    settings.register_profile(
        "ci", derandomize=True, deadline=timedelta(seconds=60),
        print_blob=True)
    settings.register_profile("dev", deadline=timedelta(seconds=60))
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))

# ~20x the slowest test of the suite on a CPU host
DEFAULT_TIME_LIMIT_S = 300.0

# the stack dump waits this long past the limit, so that a test the alarm
# ends cancels it first: faulthandler reading the frames of a thread that
# is running Python code can crash the process
DUMP_GRACE_S = 10.0

_stderr_fd = -1


class TimeLimitExceeded(BaseException):
    """Raised inside a test that outlives its time limit. A
    BaseException, so neither the code under test nor hypothesis (which
    would shrink and replay an ordinary exception) swallows it."""


def pytest_configure(config):
    global _stderr_fd
    # taken before output capture starts, so the stack dump reaches the
    # terminal even when the test never returns to report it
    _stderr_fd = os.dup(sys.__stderr__.fileno())


def pytest_unconfigure(config):
    os.close(_stderr_fd)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else DEFAULT_TIME_LIMIT_S

    def expired(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} exceeded its time limit of {limit:g} s")

    previous = signal.signal(signal.SIGALRM, expired)
    faulthandler.dump_traceback_later(limit + DUMP_GRACE_S,
                                      file=_stderr_fd)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
