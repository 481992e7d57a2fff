"""Host time of a decode round beyond its device program: the mean host
span of a decode round (the engine's ``decode`` events) minus the mean
device time of one decode-chunk program run (trace)."""
from bench import readers


def read(ctx):
    runs = readers.decode_runs(ctx)
    if not runs:
        return None
    rounds = readers.decode_rounds(ctx)
    host = readers.mean([rounds[r][1] - rounds[r][0] for _, _, r in runs])
    dev = readers.mean([t - s for s, t, _ in runs])
    return 1e3 * (host - dev)
