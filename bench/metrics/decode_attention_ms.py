"""Device time of the ops under the model's ``attention`` scope (page
gather, scores, mask, softmax, weighted sum) in the decode-chunk programs,
per decode step, as ``decode_step_ms`` counts steps."""
from bench import spans


def read(ctx):
    return spans.decode_scope_ms(ctx, ("attention",))
