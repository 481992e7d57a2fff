"""Device time of the decode-chunk programs over the decode steps they ran
(``chunk`` steps a run)."""
from bench import readers


def read(ctx):
    runs = readers.decode_runs(ctx)
    if not runs:
        return None
    steps = len(runs) * int(ctx.serving["chunk"])
    return 1e3 * sum(t - s for s, t, _ in runs) / steps
