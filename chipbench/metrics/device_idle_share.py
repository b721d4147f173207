"""1 minus the union of device-op intervals over the traced window."""


def read(run):
    s = run.trace
    return 100.0 * (1.0 - s.busy_s / s.window_s) if s else None
