"""99th percentile of the gap between consecutive streamed tokens, over
every gap of every request due in the window (host clock)."""
from stats import percentile, token_gaps_s


def read(run):
    v = percentile(token_gaps_s(run), 99)
    return None if v is None else 1e3 * v
