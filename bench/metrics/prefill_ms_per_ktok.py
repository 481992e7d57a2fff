"""Device time of the admit programs per 1000 true prompt tokens."""
from bench import readers


def read(ctx):
    runs = readers.admit_runs(ctx)
    tokens = sum(n for _, _, n in runs)
    if not tokens:
        return None
    return 1e3 * sum(t - s for s, t, _ in runs) / (tokens / 1e3)
