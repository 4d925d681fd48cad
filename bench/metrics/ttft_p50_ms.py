"""Median time to first token of the requests due in the window, ms."""
from readings import pct, ttft_s


def read(run):
    return pct(ttft_s(run), 50) * 1e3 if run.open_loop else None
