"""The device's idle share of the traced window: 1 - busy / window."""


def read(ctx):
    trace = ctx["trace"]
    if trace.window_s <= 0 or trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
