"""Share of the prompt tokens admitted in the window whose K/V came from
the prefix cache (``prefill_tokens_saved`` over ``prefill_tokens_total``
of ``GET /status``)."""
from stats import counter_delta


def read(run):
    total = counter_delta(run, "prefill_tokens_total")
    saved = counter_delta(run, "prefill_tokens_saved")
    if not total or saved is None:
        return None
    return 100.0 * saved / total
