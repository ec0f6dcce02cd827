"""Tokens streamed to the clients inside the window, over the window's
length (host clock)."""
from stats import tokens_in_window


def read(run):
    w0, w1 = run["window"]
    n = tokens_in_window(run)
    return n / (w1 - w0) if n else None
