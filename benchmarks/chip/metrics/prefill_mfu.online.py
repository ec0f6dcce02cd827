"""Prefill's share of the chip's bf16 peak: the prompt tokens computed
in the window (admitted minus served from the prefix cache) times the
operations of one prompt token (the model family's count), over the
time the scheduler spent in prefill (``repro_step_prefill_seconds``).
A prompt token's attention context is taken as the middle of the
uncached part of each prompt due in the window, with the cached part
leading."""
import numpy as np

from stats import counter_delta, hist_delta, window_requests


def read(run):
    total = counter_delta(run, "prefill_tokens_total")
    saved = counter_delta(run, "prefill_tokens_saved")
    busy = hist_delta(run, "repro_step_prefill_seconds")
    reqs = window_requests(run)
    if (not total or saved is None or busy is None or not reqs
            or not run["peak"]):
        return None
    f = saved / total
    n = np.array([r["prompt_len"] for r in reqs], float)
    ctx = float(np.mean(n * (1.0 + f) / 2.0))
    flops = (total - saved) * run["family"].prompt_flops_per_token(
        run["spec"], ctx)
    return 100.0 * flops / busy[1] / run["peak"]["bf16_flops_per_s"]
