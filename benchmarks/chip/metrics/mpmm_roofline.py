"""Kernels: the least time of the layers the compiled step sends to the
``mpmm`` kernel over that kernel's device time in the traced stretch, %."""
import workcount


def read(run):
    return workcount.kernel_roofline(run, "mpmm")
