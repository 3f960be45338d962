"""Self time of the program's ``ask`` spans (the ask less its pool,
encoding, GP calls and ranking: the history's hash, the NaN guards, the
benchmark's capture hooks), per ask of the window."""

import program_spans


def read(ctx):
    return program_spans.per_ask_ms(ctx, ("ask",), self_time=True)
