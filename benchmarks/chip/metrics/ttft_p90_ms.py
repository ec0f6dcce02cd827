"""90th percentile of time to first token, from each request's due time,
over every request due in the window (host clock, load generator)."""
from stats import percentile, ttfts_s


def read(run):
    v = percentile(ttfts_s(run), 90)
    return None if v is None else 1e3 * v
