"""Time an investigation spends folding the store's records into its
history before its first ask (the program's ``engine.resume`` span), per
investigation that resumed in the window.  It counts against
``trials_per_s`` but lies in no trial."""

import program_spans


def read(ctx):
    seconds, n = program_spans.total(ctx, ("engine.resume",))
    return 1e3 * seconds / n if n else None
