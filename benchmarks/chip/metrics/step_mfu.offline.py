"""The whole serving step's share of the chip's bf16 peak: tokens
streamed per second in the window times the operations of one decode
token (every matmul, the head, attention over the mean live context),
over the peak."""
from stats import decode_batch, live_kv_rows, tokens_in_window
from workcount import decode_flops_per_token


def read(run):
    n = tokens_in_window(run)
    batch = decode_batch(run)
    rows = live_kv_rows(run)
    if not n or not batch or rows is None or not run["peak"]:
        return None
    w0, w1 = run["window"]
    flops = decode_flops_per_token(run["spec"], rows / batch)
    return 100.0 * n / (w1 - w0) * flops / run["peak"]["bf16_flops_per_s"]
