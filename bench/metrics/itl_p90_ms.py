"""90th percentile of all gaps between consecutive tokens of a request
that close in the window, pooled over requests, ms. Every slot that
decodes in a step gets the same gap, so the samples are steps, some
hundreds of them in a window; the 90th percentile keeps tens beyond it,
and lies among the steps that launched a prefill."""
from readings import pct, token_gaps_s


def read(run):
    v = pct(token_gaps_s(run), 90)
    return None if v is None else v * 1e3
