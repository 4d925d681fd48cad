"""Scheduler: 90th percentile of due time to admission into an engine
slot (``Request.exec_start``) of the requests due in the window, ms."""
from readings import pct, queue_wait_s


def read(run):
    return pct(queue_wait_s(run), 90) * 1e3 if run.open_loop else None
