"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's op intervals) / window, from the profiler
trace (``tracereduce``)."""
from stats import idle_share


def read(run):
    return idle_share(run)
