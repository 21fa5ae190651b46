"""The bit-plane GeMV kernels' share of their roofline over the traced
window: the steps the trace holds (its kernel launches over the launches of
one step) times the least time of one step's launches (each launch's packed
planes, scales, input codes and f32 outputs over HBM bandwidth, or one
multiply-add per weight and row over peak FLOP/s, whichever is larger),
over those kernels' device time. Nothing when the trace holds none."""
from harness.trace import BITPLANE_KERNELS, kernel_count, kernel_s


def read(ctx):
    t = kernel_s(ctx["trace"], BITPLANE_KERNELS)
    if t <= 0:
        return None
    steps = (kernel_count(ctx["trace"], BITPLANE_KERNELS)
             / ctx["steps"]["launches_per_step"])
    return 100.0 * steps * ctx["steps"]["kernel_step_s"] / t
