"""The share of the traced window in which no op ran on the device: one
minus the union of the device's op intervals over the window."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
