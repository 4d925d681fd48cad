"""What the metric readers share: the samples of a run's window.

Every time is on the run's clock (seconds); the window is
``[run.t0, run.t1)``.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values, q: float) -> Optional[float]:
    """The exact ``q``-th percentile of all ``values`` (None if empty)."""
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def ttft_s(run) -> List[float]:
    """Time to first token of every request due in the window, from its
    due time; one with no first token by the window's end counts with
    the time it had waited by then."""
    return [min(r.first if r.first is not None else run.t1, run.t1) - r.due
            for r in run.due_in_window]


def queue_wait_s(run) -> List[float]:
    """Due time to the engine's admission (``Request.exec_start``) of
    every request due in the window, counted as for ``ttft_s``."""
    out = []
    for r in run.due_in_window:
        start = r.req.exec_start
        out.append(min(start if start is not None else run.t1, run.t1)
                   - r.due)
    return out


def token_gaps_s(run) -> List[float]:
    """Every gap between consecutive tokens of a request that closes
    inside the window."""
    out = []
    for r in run.requests:
        s = r.stamps
        out.extend(b - a for a, b in zip(s, s[1:]) if run.in_window(b))
    return out


def window_tokens(run) -> int:
    return sum(run.in_window(t) for r in run.requests for t in r.stamps)


def step_seconds(steps) -> float:
    return sum(s.end - s.start for s in steps)
