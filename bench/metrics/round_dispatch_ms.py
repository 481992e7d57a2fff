"""Mean host time of the window's decode rounds in ``engine.dispatch``:
the block-table and slot-state uploads and the decode-chunk call until it
returns (``ServeReport.spans``, engine clock)."""
from bench import spans


def read(ctx):
    return spans.round_phase_ms(ctx, "engine.dispatch")
