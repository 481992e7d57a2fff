"""Model FLOPs of the window's admit prefills (causal, true lengths, the
last position's logits only) over their device time at the bf16 peak."""
from bench import readers


def read(ctx):
    runs = readers.admit_runs(ctx)
    flops = sum(ctx.work.prefill_flops(ctx.hp, n) for _, _, n in runs)
    return readers.mfu(flops, sum(t - s for s, t, _ in runs), ctx)
