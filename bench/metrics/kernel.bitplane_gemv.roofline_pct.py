"""The bit-plane GeMV kernels' share of their roofline over the traced
window: the steps the trace holds (its kernel launches over the launches of
one step) times the least time of one step's launches (each launch's packed
planes, scales, input codes and f32 outputs over HBM bandwidth, or one
multiply-add per weight and row over peak FLOP/s, whichever is larger),
over those kernels' device time. The launches and their least time are
the `bitplane_gemv` kernel set's, as the cell's block family counts them:
a cell lists this metric only where its family runs that set, so a family
that counts none is an error. Nothing when the trace holds no launch."""
from harness.trace import BITPLANE_KERNELS, kernel_count, kernel_s


def read(ctx):
    step = ctx["steps"]["kernels"]["bitplane_gemv"]
    t = kernel_s(ctx["trace"], BITPLANE_KERNELS)
    if t <= 0:
        return None
    steps = (kernel_count(ctx["trace"], BITPLANE_KERNELS)
             / step["launches_per_step"])
    return 100.0 * steps * step["kernel_step_s"] / t
