"""Time in the store's round trips (every ``store.*`` span of the program:
interning, the claim, writing values, the record, reading the sample back),
per trial completed in the window."""

import program_spans


def read(ctx):
    return program_spans.per_trial_ms(ctx, "store.")
