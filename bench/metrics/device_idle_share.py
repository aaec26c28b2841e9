"""Device: the share of the traced window in which no operation ran on
the device (one minus the union of operation intervals over the window)."""


def read(run):
    w = run.trace.window_s
    return (1.0 - run.trace.busy_s / w) * 100.0 if w > 0 else None
