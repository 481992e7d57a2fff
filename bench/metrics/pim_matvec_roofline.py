"""``pim_matvec`` calls in the decode programs: least time (the larger of
the call's FLOPs at the bf16 peak and its bytes at the HBM bandwidth, from
the model's shapes) over kernel time, in %."""
from bench import readers, trace


def read(ctx):
    return readers.kernel_share(ctx, "pim_matvec", trace.DECODE)
