"""All-gather device time inside the decode-chunk programs over their
device time, in %, averaged over the chips."""
from bench import readers, trace


def read(ctx):
    shares = []
    for chip in range(len(ctx.trace.chips)):
        mods = ctx.trace.modules(trace.DECODE, chip)
        if not len(mods):
            continue
        ops = ctx.trace.ops_in(mods, chip).select(
            lambda n: trace.op_kind(n).startswith("all-gather"))
        if len(ops):
            shares.append(100.0 * ops.seconds / mods.seconds)
    return readers.mean(shares)
