"""Dispatch and device: percent of the traced window in which no
operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns() / tr.window_ns)
