"""Host time the ask spends encoding configurations into the unit cube,
the history (``ask.encode.history``) and the pool (``ask.encode.pool``),
from the program's spans, per ask of the window."""

import program_spans


def read(ctx):
    return program_spans.per_ask_ms(
        ctx, ("ask.encode.history", "ask.encode.pool"))
