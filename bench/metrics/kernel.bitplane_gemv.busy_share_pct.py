"""The bit-plane GeMV kernels' device time as a share of the device's busy
time in the window (trace)."""
from harness.trace import BITPLANE_KERNELS, kernel_s


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    if busy <= 0:
        return None
    return 100.0 * kernel_s(ctx["trace"], BITPLANE_KERNELS) / busy
