"""Model FLOPs of the window's decode steps (live slots only, each at its
true context) over the decode programs' device time at the bf16 peak of
every chip."""
from bench import readers


def read(ctx):
    runs = readers.decode_runs(ctx)
    flops = sum(ctx.work.decode_flops(ctx.hp, c) for _, _, r in runs
                for c in readers.decode_step_contexts(ctx, r))
    return readers.mfu(flops, sum(t - s for s, t, _ in runs), ctx)
