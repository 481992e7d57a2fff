"""Mean host time of the window's decode rounds in ``engine.emit``: from
the chunk's outputs on the host to the round's end (token loop, slot-state
copies, decode events, finishes, counter sample; ``ServeReport.spans``,
engine clock)."""
from bench import spans


def read(ctx):
    return spans.round_phase_ms(ctx, "engine.emit")
