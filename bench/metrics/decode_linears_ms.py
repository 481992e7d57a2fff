"""Device time of the ops under the model's ``linear`` and ``lm_head``
scopes (every weight product, its pads and its kernel call, and the head)
in the decode-chunk programs, per decode step, as ``decode_step_ms`` counts
steps."""
from bench import spans


def read(ctx):
    return spans.decode_scope_ms(ctx, ("linear", "lm_head"))
