"""Mean host time of the window's decode rounds in ``engine.schedule``
(queue management, admission decisions, retirements, page top-up), less
the ``engine.admit`` spans nested in it: the engine's own spans
(``ServeReport.spans``, engine clock)."""
from bench import spans


def read(ctx):
    return spans.round_phase_ms(ctx, "engine.schedule")
