"""Mean wait from enqueue to admission in the window: the scheduler's
``repro_queue_wait_seconds`` histogram (count and sum)."""
from stats import hist_mean


def read(run):
    v = hist_mean(run, "repro_queue_wait_seconds")
    return None if v is None else 1e3 * v
