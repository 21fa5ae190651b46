"""The whole step's share of the chip's roofline: the least time the
window's useful inner steps could take (each the larger of its FLOPs over
peak FLOP/s and its minimum bytes over HBM bandwidth, from shapes), over
the window's length. Nothing when the window ran no step."""


def read(ctx):
    least = ctx["steps"]["step_roofline_s"]
    if least <= 0:
        return None
    return 100.0 * least / ctx["window_s"]
