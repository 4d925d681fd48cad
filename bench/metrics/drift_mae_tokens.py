"""Scheduler: mean absolute error of the admission-time output estimate
against the tokens served, over the requests that completed in the
window, tokens. The drift tracker's samples of these requests
(``DriftTracker.stats(after=t0, before=t1).mae``), read from the same
fields of the requests the run kept."""


def read(run):
    errors = [abs(r.req.estimate.est_output_tokens
                  - r.req.observed_output_tokens)
              for r in run.requests if run.in_window(r.req.exec_end)]
    return sum(errors) / len(errors) if errors else None
