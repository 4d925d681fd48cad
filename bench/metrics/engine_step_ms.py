"""Engine loop: mean wall time of one ``ServingEngine.step`` in the
window, from the harness's span around each call, ms."""
from readings import step_seconds


def read(run):
    steps = run.window_steps
    return step_seconds(steps) / len(steps) * 1e3 if steps else None
