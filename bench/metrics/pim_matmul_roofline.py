"""``pim_matmul`` calls in the admit programs: least time over kernel
time, in %, by the rule of ``pim_matvec_roofline``."""
from bench import readers, trace


def read(ctx):
    return readers.kernel_share(ctx, "pim_matmul", trace.ADMIT)
