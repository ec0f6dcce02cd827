"""The decode step's share of its roofline: the least time the chip
could take for the window's mean step (the larger of its operations
over the bf16 peak and its bytes over the HBM bandwidth: every weight
once in the compute dtype plus the live KV blocks), over the mean
measured step (``repro_step_decode_seconds``). The counts are the
model family's (``run["family"]``)."""
from stats import decode_batch, hist_mean, live_kv_rows
from workcount import decode_least_s


def read(run):
    step = hist_mean(run, "repro_step_decode_seconds")
    batch = decode_batch(run)
    rows = live_kv_rows(run)
    if not step or not batch or rows is None or not run["peak"]:
        return None
    fam, spec = run["family"], run["spec"]
    least, _ = decode_least_s(fam, spec, batch, rows / batch,
                              rows * fam.kv_row_bytes(spec), run["peak"])
    return 100.0 * least / step
