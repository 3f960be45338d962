"""Host time the ask spends building its candidate pool
(``Optimizer._unseen_candidates``: the unseen points of a finite space),
from the program's ``ask.pool`` spans, per ask of the window."""

import program_spans


def read(ctx):
    return program_spans.per_ask_ms(ctx, ("ask.pool",))
