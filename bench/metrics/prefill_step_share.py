"""Engine loop: share of the window's wall time spent in steps that
launched a prefill (the engine's ``n_prefill_launches`` moved), %."""
from readings import step_seconds


def read(run):
    steps = run.window_steps
    if not steps:
        return None
    return 100.0 * step_seconds([s for s in steps if s.launches]) / run.window_s
