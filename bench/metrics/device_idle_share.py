"""Device: one minus the union of device-op intervals over the traced
window, %."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device.busy_s / run.device.window_s)
