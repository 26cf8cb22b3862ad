"""Score kernel: least time the pass could take at the chip's HBM
bandwidth over the kernel's device time in the trace, percent."""

from bench import readers


def read(run):
    return readers.score_kernel_roofline(run)
