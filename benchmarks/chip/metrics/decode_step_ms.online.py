"""Mean time of one batched decode step in the window, dispatch to
logits ready (the scheduler's ``repro_step_decode_seconds``)."""
from stats import hist_mean


def read(run):
    v = hist_mean(run, "repro_step_decode_seconds")
    return None if v is None else 1e3 * v
