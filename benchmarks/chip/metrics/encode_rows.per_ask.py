"""Rows the ask encoded into the unit cube (the program's ``encode.rows``:
trials new since the last ask, a finite space's enumeration once per
investigation, a sampled pool row by row), per ask of the window.  Rows
gathered from the adapter's encodings count apart, as
``encode.rows_reused``."""

import program_spans


def read(ctx):
    counts = program_spans.counters(ctx)
    asks = program_spans.asks(ctx)
    if not counts or not asks or "encode.rows" not in counts:
        return None
    return counts["encode.rows"] / asks
